"""Seeded host arrival stream of a consolidated multi-VM host.

A traffic mix is a JSON file under ``bench/traffic/`` (its name is the
mix's name). It names one MSR-family volume per VM (parameters in
``bench/traffic/families.json``), how the host's arrivals split over the
VMs, and how long the stream is:

    {"vms": ["hm_1", ...], "requests_per_vm": 20000, "passes": 105,
     "vm_share_zipf": 0.0, "scale": 1.0, "addr_stride": 1048576}

Each pass is the paper's mix anew: every VM's volume is generated from
its own seed, with ``requests_per_vm * len(vms)`` requests split over
the VMs by Zipf(``vm_share_zipf``) over their positions in ``vms`` (0
gives equal shares), and the per-VM streams are interleaved into one
hypervisor arrival order drawn from the seed, each VM's own order kept.
So a VM's count in a resize window varies from window to window as
arrivals do. The same seed gives the same stream.

The per-volume generator follows ``repro.traces.generators.generate``:
the same draws in the same order, so every volume equals the program's,
with the RAW step vectorised.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import numpy as np

FAMILIES = Path(__file__).resolve().parents[1] / "traffic" / "families.json"


@dataclasses.dataclass(frozen=True)
class VolumeSpec:
    read_ratio: float = 0.7
    working_set: int = 4096
    zipf_a: float = 1.1
    sequential: float = 0.0
    raw_fraction: float = 0.0
    cold_fraction: float = 0.0
    write_burst: float = 0.0


@dataclasses.dataclass
class Stream:
    """The host arrival stream: one entry per block request."""
    addr: np.ndarray        # int32 [N]
    is_write: np.ndarray    # bool  [N]
    vm: np.ndarray          # int32 [N]

    def __len__(self) -> int:
        return int(self.addr.shape[0])

    def slice(self, start: int, stop: int) -> "Stream":
        return Stream(self.addr[start:stop], self.is_write[start:stop],
                      self.vm[start:stop])


def load_families(path: Path = FAMILIES) -> dict[str, VolumeSpec]:
    raw = json.loads(Path(path).read_text())
    return {k: VolumeSpec(**v) for k, v in raw.items() if k != "about"}


def volume(spec: VolumeSpec, n: int, seed, addr_offset: int = 0):
    """One VM's volume: ``(addr int32 [n], is_write bool [n])``."""
    rng = np.random.default_rng(seed)
    addr = np.zeros(n, np.int64)
    is_write = rng.random(n) >= spec.read_ratio
    perm = rng.permutation(spec.working_set)
    n_seq = int(n * spec.sequential)
    n_rand = n - n_seq
    # Zipf re-references over the permuted working set
    p = np.arange(1, spec.working_set + 1, dtype=np.float64) ** (-spec.zipf_a)
    p /= p.sum()
    addr[:n_rand] = perm[rng.choice(spec.working_set, size=n_rand, p=p)]
    if n_seq:
        addr[n_rand:] = spec.working_set + np.arange(n_seq)
        is_write[n_rand:] = rng.random(n_seq) >= spec.read_ratio
    order = rng.permutation(n)
    addr = addr[order]
    is_write = is_write[order]
    if spec.cold_fraction > 0:       # one-shot reads
        reads = np.nonzero(~is_write)[0]
        k = int(len(reads) * spec.cold_fraction)
        if k:
            pick = rng.choice(reads, size=k, replace=False)
            addr[pick] = spec.working_set + n + np.arange(k)
    if spec.write_burst > 0:         # one-shot writes
        writes = np.nonzero(is_write)[0]
        k = int(len(writes) * spec.write_burst)
        if k:
            pick = rng.choice(writes, size=k, replace=False)
            addr[pick] = spec.working_set + 2 * n + np.arange(k)
    if spec.raw_fraction > 0:        # reads of one of the last 8 writes
        write_pos = np.nonzero(is_write)[0]
        reads = np.nonzero(~is_write)[0]
        k = int(len(reads) * spec.raw_fraction)
        if k and write_pos.size:
            pick = rng.choice(reads, size=k, replace=False)
            before = np.searchsorted(write_pos, pick)   # writes before each
            pick, before = pick[before > 0], before[before > 0]
            back = rng.integers(0, np.minimum(8, before))
            addr[pick] = addr[write_pos[before - 1 - back]]
    out = addr + int(addr_offset)
    if out.size and (out.min() < 0 or out.max() >= 2**31):
        raise ValueError(f"addresses [{out.min()}, {out.max()}] do not fit "
                         f"int32 at offset {addr_offset}")
    return out.astype(np.int32), is_write


def interleave(parts, seed) -> Stream:
    """Interleave per-VM ``(addr, is_write)`` streams into one arrival
    order drawn from ``seed``: every arrival order of the requests is as
    likely, and each VM's own order is kept."""
    lengths = [len(a) for a, _ in parts]
    vm = np.random.default_rng(seed).permutation(
        np.repeat(np.arange(len(parts), dtype=np.int32), lengths))
    pos = np.argsort(vm, kind="stable")   # each VM's slots, in order
    addr = np.empty(vm.size, np.int32)
    is_write = np.empty(vm.size, bool)
    addr[pos] = np.concatenate([a for a, _ in parts])
    is_write[pos] = np.concatenate([w for _, w in parts])
    return Stream(addr, is_write, vm)


def vm_counts(total: int, num_vms: int, zipf: float) -> np.ndarray:
    """``total`` requests split by Zipf(``zipf``) over the VMs' positions
    (largest-remainder rounding)."""
    w = np.arange(1, num_vms + 1, dtype=np.float64) ** (-zipf)
    exact = total * w / w.sum()
    out = np.floor(exact).astype(np.int64)
    rest = total - int(out.sum())
    out[np.argsort(-(exact - out), kind="stable")[:rest]] += 1
    return out


def _seed(*keys) -> int:
    return int(np.random.SeedSequence([int(k) for k in keys])
               .generate_state(1, np.uint64)[0])


def stream(mix: dict, seed: int, passes: int | None = None) -> Stream:
    """The host stream of a traffic mix (a parsed ``bench/traffic`` file)."""
    fam = load_families()
    names = mix["vms"]
    per_pass = vm_counts(mix["requests_per_vm"] * len(names), len(names),
                         mix.get("vm_share_zipf", 0.0))
    scale = mix.get("scale", 1.0)
    stride = mix.get("addr_stride", 2**20)
    out = []
    for p in range(mix["passes"] if passes is None else passes):
        parts = []
        for v, name in enumerate(names):
            spec = fam[name]
            if scale != 1.0:
                spec = dataclasses.replace(
                    spec, working_set=max(int(spec.working_set * scale), 16))
            parts.append(volume(spec, int(per_pass[v]), _seed(seed, p, v),
                                addr_offset=v * stride))
        out.append(interleave(parts, _seed(seed, p, len(names))))
    return Stream(np.concatenate([s.addr for s in out]),
                  np.concatenate([s.is_write for s in out]),
                  np.concatenate([s.vm for s in out]))
