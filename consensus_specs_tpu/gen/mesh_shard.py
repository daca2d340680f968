"""Device-mesh sharded vector generation.

SURVEY §2.6: the reference fans vector generation across workers with
pathos pools and `make -j` across hosts.  The TPU-native equivalent
treats the device mesh as the scheduling substrate: the round-robin
case→worker assignment (the same contract as
`scripts/gen_vectors.py --shard I/N`) is computed ON the mesh with a
shard_map iota — each device lane emits the case indices congruent to
its mesh position — and the host materializes one output shard per
device.  The shards are disjoint and their on-disk union is
byte-identical to the serial run (the INCOMPLETE-tag/resume semantics
of gen.runner make the union safe, exactly as for the process
fan-out).
"""
from __future__ import annotations

import numpy as np

from .runner import run_generator
from .typing import TestProvider


def mesh_case_assignment(mesh, n_cases: int) -> list[list[int]]:
    """Per-device case-index lists, computed by the mesh itself.

    Device d's lane writes indices d, d+n_dev, 2n_dev+d, ... — the
    ``--shard d/n_dev`` round-robin contract — via a shard_map iota, so
    the scheduling artifact executes on the mesh rather than being host
    arithmetic."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from ..parallel.collectives import AXIS

    n_dev = int(np.prod(list(mesh.shape.values())))
    per = -(-n_cases // n_dev) if n_cases else 0
    if per == 0:
        return [[] for _ in range(n_dev)]

    def body():
        d = jax.lax.axis_index(AXIS)
        idx = d + jnp.arange(per, dtype=jnp.int32) * n_dev
        return jnp.where(idx < n_cases, idx, -1)[None]

    fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(),
                               out_specs=P(AXIS, None), check_vma=False))
    rows = np.asarray(jax.device_get(fn()))
    return [[int(i) for i in row if i >= 0] for row in rows]


def shard_providers(providers, i0: int, n: int):
    """THE round-robin shard filter (the ``--shard i0/n`` contract):
    within each provider's enumeration order, keep cases whose index is
    ≡ i0 (mod n).  scripts/gen_vectors.py and the mesh fan-out both use
    this one implementation, so host-level and device-level sharding
    compose without drift."""
    out = []
    for provider in providers:
        def make_cases(p=provider):
            for idx, case in enumerate(p.make_cases()):
                if idx % n == i0:
                    yield case
        out.append(TestProvider(prepare=provider.prepare,
                                make_cases=make_cases))
    return out


def count_cases(providers_fn) -> int:
    n = 0
    for provider in providers_fn():
        provider.prepare()
        n += sum(1 for _ in provider.make_cases())
    return n


def run_generator_mesh_sharded(runner_name: str, providers_fn, out_dir,
                               mesh, extra_args=()) -> dict:
    """Generate a runner's cases as one shard per mesh device and merge
    the diagnostics (written back over the per-shard diagnostics file,
    which each run_generator call rewrites).  Residue d of the
    round-robin belongs to mesh device d — mesh_case_assignment is the
    executable statement of that ownership.  `providers_fn` is called
    once per shard; each shard walks the (deterministic) enumeration
    and keeps its residue class, the same cost shape as the process
    fan-out."""
    import json
    import os

    n_dev = int(np.prod(list(mesh.shape.values())))
    merged = {"generated": 0, "skipped": 0, "failed": 0,
              "shards": n_dev, "durations": {}, "slow": []}
    for dev in range(n_dev):
        shard = shard_providers(providers_fn(), dev, n_dev)
        diag = run_generator(
            runner_name, shard,
            args=["-o", str(out_dir), *extra_args])
        for key in ("generated", "skipped", "failed"):
            merged[key] += diag.get(key, 0)
        merged["durations"].update(diag.get("durations", {}))
        merged["slow"].extend(diag.get("slow", []))
    # the last shard's run_generator left only ITS diagnostics on disk;
    # replace with the merged view so failures in any shard are visible
    diag_path = os.path.join(str(out_dir),
                             f"diagnostics_{runner_name}.json")
    if os.path.exists(diag_path):
        with open(diag_path, "w") as f:
            json.dump(merged, f, indent=1, sort_keys=True)
    return merged
