"""Pallas TPU kernels for the framework's compute hot spots.

Each subpackage ships the kernel (`kernel.py`: pl.pallas_call + explicit
BlockSpec tiling), a jitted wrapper (`ops.py`), and a pure-jnp or numpy
oracle (`ref.py`) the kernel is tested against (interpret mode executes
the kernel bodies on CPU; the same kernels compile natively on TPU).

  * reuse_distance   — tiled windowed distinct-count (POD/URD/TRD), the
                       paper's PARDA hot path on the TPU VPU
  * popularity       — fused Eq. 1 exp + segment reduction
  * maintenance      — ETICA's between-interval promote/evict/clean
                       scatters over stacked [V, S, W] states + the fused
                       per-interval maintenance dispatch
  * flash_attention  — blocked causal/windowed attention fwd (GQA-native)
  * decode_attention — paged flash-decode over the two-tier KV pool
                       (scalar-prefetched page tables)

Every kernel entry point takes ``interpret: bool | None = None``;
``None`` resolves through :func:`use_interpret`: compiled on a TPU
backend, the Pallas interpreter everywhere else.
"""
from __future__ import annotations

import os


def env_flag(name: str) -> bool | None:
    """Tri-state env override: unset -> None, ``0``/``false`` (any
    case) / empty -> False, anything else -> True."""
    env = os.environ.get(name)
    if env is None:
        return None
    return env.lower() not in ("0", "false", "")


def on_tpu() -> bool:
    import jax
    return jax.default_backend() == "tpu"


def refuse_on_tpu(name: str, value: str) -> None:
    """Raise if env override ``name`` would take a TPU run off its
    compiled kernels (``value`` names what it would switch to)."""
    raise RuntimeError(
        f"{name} is set on a TPU backend; it would run {value} instead of "
        f"the compiled Pallas kernels. Unset it: TPU runs always use the "
        f"compiled kernels.")


def use_interpret() -> bool:
    """Pallas interpret mode unless running on a real TPU backend.

    Off TPU, ``ETICA_PALLAS_INTERPRET=1`` forces the interpreter (CI's
    kernels-interpret job runs the whole suite this way on CPU) and
    ``=0`` asks for compiled Pallas. On a TPU backend the kernels are
    always compiled, and an override that would force the interpreter
    raises rather than quietly slowing the run.
    """
    forced = env_flag("ETICA_PALLAS_INTERPRET")
    if on_tpu():
        if forced:
            refuse_on_tpu("ETICA_PALLAS_INTERPRET", "the Pallas interpreter")
        return False
    return True if forced is None else forced


def resolve_interpret(interpret: bool | None) -> bool:
    """An entry point's ``interpret`` argument: ``None`` ->
    :func:`use_interpret`, an explicit bool is kept."""
    return use_interpret() if interpret is None else bool(interpret)
