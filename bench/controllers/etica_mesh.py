"""The system under test for an ``"controller": "etica_mesh"``
configuration: ``repro.core.EticaCache`` as ``bench/controllers/etica.py``
builds it, with the VM axis sharded over ``mesh_shards`` devices
(``EticaConfig.mesh``, ``repro.launch.mesh.make_vm_mesh``): the datapath,
the fused maintenance and the resize run shard-local, and the sizing's
reuse decompositions run one row block per device, gathered on the host.

Everything but ``build`` and ``state`` is the etica adapter's.
"""
from __future__ import annotations

import dataclasses

from bench.controllers import etica
from bench.controllers.etica import (observe, run, signature,  # noqa: F401
                                     sync, warm_lengths)


def build(cfg: dict, num_vms: int):
    from repro.core import EticaCache
    from repro.launch.mesh import make_vm_mesh
    # the etica adapter's controller, built for no VM (it holds nothing):
    # only its configuration is taken
    plain = etica.build(cfg, 0).cfg
    return EticaCache(dataclasses.replace(
        plain, mesh=make_vm_mesh(cfg["mesh_shards"])), num_vms)


def state(ctrl) -> dict:
    """The etica adapter's state without the dead-VM rows that pad the
    VM count to a multiple of the mesh size."""
    out = etica.state(ctrl)
    return {k: x[: ctrl.num_vms] for k, x in out.items()}
