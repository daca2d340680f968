"""Batched G1 aggregation sweep: many ragged point lists -> one sum each.

The committee pubkey sums of a scheduler flush (sigpipe/cache.py) are
O(committee) point adds per signature set — ~512 host adds per sync
aggregate — and a flush carries many sets.  `g1_add_sweep` fuses ALL of
them into one padded ragged-segment tree reduction: the lists are packed
into a single [segments, length] Jacobian limb tensor (infinity-padded,
both axes rounded to powers of two so XLA only ever sees log-many
shapes), then reduced along the length axis by one scanned `point_add`
(ops/curve_jax.py `g1_sum_axis`, the complete Jacobian arithmetic
unchanged) — one compiled program per shape, where a host-driven
halving tree compiled one add kernel per halving shape.

Engine selection (G1_SWEEP_MODE env: "jax" | "oracle") is the same
platform split as msm.MSM_MODE / pairing_jax.PAIRING_MODE: the limb
kernels are a tens-of-seconds XLA compile per shape on a small CPU host
(fine once, cached on accelerators), so CPU defaults to the vectorized
host oracle — one call per flush over crypto/curve.py ints — and
accelerators default to the jax sweep.  Either way the call shape seen
by the scheduler is identical: one batched invocation per flush, never
a per-set Python loop (that loop is the *fallback* of the
`ops.g1_aggregate` resilience dispatch site, and is what
sigpipe.metrics' `host_point_adds` counts).

Multi-chip: with a >1-device verify mesh the padded segment axis is
partitioned over the mesh (parallel/shard_verify.py `shard_jobs`) —
each device tree-sums its own segments with zero cross-device traffic,
inside the same single dispatch; a 1-device mesh is byte-identical to
the unsharded path.

Oracle: summing each list with crypto/curve.py `Point.__add__`.
"""
from __future__ import annotations

import os as _os

from ..crypto import curve as cv

# resolved LAZILY (first sweep call): the env var is read at resolve
# time, not import time, so tests/benches that flip G1_SWEEP_MODE in
# the environment are not order-dependent on when this module was first
# imported.  Assigning the global directly still wins (the test-fixture
# idiom); `reset_mode()` forgets a cached choice.
G1_SWEEP_MODE = None


def reset_mode() -> None:
    """Forget the cached engine choice: the next call re-reads the
    G1_SWEEP_MODE env var and the active jax backend."""
    global G1_SWEEP_MODE
    G1_SWEEP_MODE = None


def _resolve_mode() -> str:
    global G1_SWEEP_MODE
    if G1_SWEEP_MODE is None:
        env = _os.environ.get("G1_SWEEP_MODE")
        if env:
            G1_SWEEP_MODE = env
        else:
            import jax
            G1_SWEEP_MODE = ("oracle" if jax.default_backend() == "cpu"
                             else "jax")
    return G1_SWEEP_MODE


def _pow2(n: int) -> int:
    return 1 << (n - 1).bit_length() if n > 1 else 1


def _oracle_sweep(point_lists):
    """Vectorized host engine: every segment summed in one call (the
    CPU stand-in for the jax sweep — same one-invocation-per-flush call
    shape, host int arithmetic inside)."""
    out = []
    for pts in point_lists:
        acc = cv.g1_infinity()
        for p in pts:
            acc = acc + p
        out.append(acc)
    return out


def _jax_sweep(point_lists):
    import numpy as np
    import jax.numpy as jnp

    from . import curve_jax as cj
    from . import fq

    n_seg = len(point_lists)
    seg_len = _pow2(max((len(pts) for pts in point_lists), default=1)
                    or 1)
    n_pad = _pow2(n_seg)
    inf = cv.g1_infinity()
    flat = []
    for pts in point_lists:
        flat.extend(pts)
        flat.extend([inf] * (seg_len - len(pts)))
    flat.extend([inf] * (seg_len * (n_pad - n_seg)))
    X, Y, Z = cj.g1_pack(flat)
    X = X.reshape(n_pad, seg_len, fq.LIMBS)
    Y = Y.reshape(n_pad, seg_len, fq.LIMBS)
    Z = Z.reshape(n_pad, seg_len, fq.LIMBS)
    # multi-chip: partition the (padded, power-of-two) segment axis
    # over the verify mesh — the halving tree below reduces along the
    # LENGTH axis, so each device sums its own segments with zero
    # cross-device traffic; a 1-device mesh is a no-op
    from ..parallel import shard_verify
    X, Y, Z = shard_verify.shard_jobs((X, Y, Z), "ops.g1_aggregate")
    # one scanned add along the segment-length axis: every segment
    # reduces in the same program, whatever its padded length
    X, Y, Z = cj.g1_sum_axis((X, Y, Z), 1)
    out = cj.g1_unpack((jnp.asarray(np.asarray(X)),
                        jnp.asarray(np.asarray(Y)),
                        jnp.asarray(np.asarray(Z))))
    return out[:n_seg]


def g1_add_sweep(point_lists):
    """Sum each list of oracle G1 Points; returns one Point per list
    (infinity for an empty list).  One batched invocation regardless of
    how many lists or how ragged their lengths."""
    point_lists = [list(pts) for pts in point_lists]
    if not point_lists:
        return []
    if _resolve_mode() == "jax":
        return _jax_sweep(point_lists)
    return _oracle_sweep(point_lists)
