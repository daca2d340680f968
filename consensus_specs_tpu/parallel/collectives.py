"""Sharded reductions over the device mesh.

The reduction shapes covering the framework's hot paths (SURVEY.md §2.6):

* sharded_balance_total — the epoch-processing scalar reduction
  (get_total_active_balance and friends): local sum + psum.
* sharded_merkle_root — hash_tree_root over a chunk tree sharded on the
  leaf axis: local subtree sweep, all_gather of subtree roots, replicated
  top sweep (the BeaconState merkleization layout).
* sharded_g1_sum — aggregate-pubkey / MSM-partial reduction: each device
  tree-sums its shard of G1 points, partial sums are all_gathered and the
  small replicated tail is tree-added.  G1 addition is the reduction op
  the ICI ring carries for big-batch BLS aggregation.

All functions are shard_map bodies over a 1-D mesh axis "data"; callers
jit them via `make_*` builders that close over the mesh.
"""
from __future__ import annotations

from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops import curve_jax as cj
from ..ops.sha256 import sha256_64byte


AXIS = "data"


# ---------------------------------------------------------------------------
# balances
# ---------------------------------------------------------------------------

def sharded_balance_total(local_balances):
    """Body: sum the local balance shard, psum across the mesh."""
    return jax.lax.psum(jnp.sum(local_balances), AXIS)


def make_balance_total(mesh: Mesh):
    return jax.jit(jax.shard_map(
        sharded_balance_total, mesh=mesh,
        in_specs=P(AXIS), out_specs=P(), check_vma=False))


# ---------------------------------------------------------------------------
# merkle
# ---------------------------------------------------------------------------

def _tree_levels(level, depth: int):
    for _ in range(depth):
        n = level.shape[0] // 2
        level = sha256_64byte(level.reshape(n, 16))
    return level


def sharded_merkle_root(local_chunks, local_depth: int):
    """Body: local subtree root, all_gather, replicated top sweep."""
    local_root = _tree_levels(local_chunks, local_depth)     # [1, 8]
    roots = jax.lax.all_gather(local_root.reshape(8), AXIS)  # [n_dev, 8]
    top_depth = int(np.log2(roots.shape[0]))
    return _tree_levels(roots, top_depth)[0]


def make_merkle_root(mesh: Mesh, chunks_per_device: int):
    local_depth = int(np.log2(chunks_per_device))
    return jax.jit(jax.shard_map(
        partial(sharded_merkle_root, local_depth=local_depth), mesh=mesh,
        in_specs=P(AXIS, None), out_specs=P(), check_vma=False))


# ---------------------------------------------------------------------------
# G1 point-set reduction
# ---------------------------------------------------------------------------

def sharded_g1_sum(X, Y, Z):
    """Body: tree-sum the local shard of Jacobian points, all_gather the
    per-device partials, tree-add the replicated tail."""
    lx, ly, lz = cj.point_sum_tree(cj.F1, (X, Y, Z))
    gx = jax.lax.all_gather(lx, AXIS)        # [n_dev, 32]
    gy = jax.lax.all_gather(ly, AXIS)
    gz = jax.lax.all_gather(lz, AXIS)
    return cj.point_sum_tree(cj.F1, (gx, gy, gz))


def make_g1_sum(mesh: Mesh):
    return jax.jit(jax.shard_map(
        sharded_g1_sum, mesh=mesh,
        in_specs=(P(AXIS, None),) * 3, out_specs=(P(),) * 3,
        check_vma=False))


def sharded_g1_ring_sum(X, Y, Z):
    """Body: RING reduction of per-device partial sums over ICI.

    Each device tree-sums its local shard, then the partials travel the
    ring with lax.ppermute: after n_dev-1 hops every device has added
    every partial, with each hop moving only one point (3x32 limb
    words) over a single neighbor link — the bandwidth shape of a ring
    all-reduce, vs all_gather's n_dev-wide fan-in.  This is the "ring
    all-gather of per-chip partial MSM buckets" pattern of SURVEY §2.6;
    big MSMs shard their buckets exactly like this.
    """
    n_dev = int(jax.lax.axis_size(AXIS))
    local = cj.point_sum_tree(cj.F1, (X, Y, Z))   # local partial
    perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]

    def hop(_i, carry):
        acc, incoming = carry
        incoming = tuple(
            jax.lax.ppermute(c, AXIS, perm) for c in incoming)
        return cj.point_add(cj.F1, acc, incoming), incoming

    # fori_loop keeps ONE hop body in the graph (an unrolled ring
    # compiles n_dev-1 point-adds inline — minutes of XLA on small
    # hosts)
    acc, _ = jax.lax.fori_loop(0, n_dev - 1, hop, (local, local))
    # [1, 32] per device -> callers see [n_dev, 32] rows, all equal
    return tuple(c[None] for c in acc)


def make_g1_ring_sum(mesh: Mesh):
    return jax.jit(jax.shard_map(
        sharded_g1_ring_sum, mesh=mesh,
        in_specs=(P(AXIS, None),) * 3,
        out_specs=(P(AXIS, None),) * 3, check_vma=False))


def sharded_msm(X, Y, Z, bits):
    """Body: the PRODUCTION sharded multi-scalar multiplication.

    Each device scalar-multiplies its local (points, scalars) shard
    with the double-and-add lanes, tree-sums the local products into
    one per-chip partial (the bucket-partial of a sharded Pippenger),
    and the partials ring-reduce over ICI exactly like
    sharded_g1_ring_sum.  This is the in-path shape g1_lincomb uses
    when the mesh engine is enabled (deneb
    polynomial-commitments.md:268 over a device mesh)."""
    n_dev = int(jax.lax.axis_size(AXIS))
    prods = cj.g1_scalar_mul((X, Y, Z), bits)
    local = cj.point_sum_tree(cj.F1, prods)
    perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]

    def hop(_i, carry):
        acc, incoming = carry
        incoming = tuple(
            jax.lax.ppermute(c, AXIS, perm) for c in incoming)
        return cj.point_add(cj.F1, acc, incoming), incoming

    acc, _ = jax.lax.fori_loop(0, n_dev - 1, hop, (local, local))
    return tuple(c[None] for c in acc)


def make_msm(mesh: Mesh):
    """Compiled sharded MSM: points sharded over the mesh's device
    axis, scalar bit-planes alongside, one replicated-sum row per
    device out."""
    return jax.jit(jax.shard_map(
        sharded_msm, mesh=mesh,
        in_specs=(P(AXIS, None), P(AXIS, None), P(AXIS, None),
                  P(AXIS, None)),
        out_specs=(P(AXIS, None),) * 3, check_vma=False))


# ---------------------------------------------------------------------------
# device placement helper
# ---------------------------------------------------------------------------

def shard_array(mesh: Mesh, arr, spec=None):
    if spec is None:
        spec = P(AXIS) if np.ndim(arr) == 1 else P(AXIS, *([None] * (np.ndim(arr) - 1)))
    return jax.device_put(jnp.asarray(arr), NamedSharding(mesh, spec))


# ---------------------------------------------------------------------------
# epoch deltas (validator-axis data parallelism)
# ---------------------------------------------------------------------------

def sharded_flag_set(local_eff_incr, local_active_cur, local_eligible,
                     local_unsl, base_per_increment, leak,
                     weight: int, weight_denominator: int,
                     head_flag: bool):
    """Standalone altair flag pass (bit-exact to the per-flag lanes
    inside ops.epoch_sweep's fused program): distinct active/eligible/
    unslashed-participating masks, the max(1, .) clamps, the leak and
    head-flag switches.  The two global reductions ride the mesh as
    psums; the reward/penalty lanes stay local.  `base_per_increment`
    and `leak` are traced (they change every epoch — baking them would
    recompile per epoch); weight/denominator/head_flag are per-flag
    constants.  Production epoch processing now runs the single fused
    sweep instead; this pass remains the mesh-collective reference the
    CPU-mesh suite pins against it."""
    eff64 = local_eff_incr.astype(jnp.int64)
    active_incr = jax.lax.psum(
        jnp.sum(jnp.where(local_active_cur, eff64, 0)), AXIS)
    active_incr = jnp.maximum(active_incr, 1)
    part_incr = jax.lax.psum(
        jnp.sum(jnp.where(local_unsl, eff64, 0)), AXIS)
    part_incr = jnp.maximum(part_incr, 1)
    base = eff64 * base_per_increment
    rewards = jnp.where(
        local_eligible & local_unsl & ~leak,
        base * weight * part_incr
        // (active_incr * weight_denominator), 0)
    if head_flag:
        penalties = jnp.zeros_like(base)
    else:
        penalties = jnp.where(
            local_eligible & ~local_unsl,
            base * weight // weight_denominator, 0)
    return rewards, penalties


def make_flag_set(mesh: Mesh, weight: int, weight_denominator: int,
                  head_flag: bool):
    """Compiled flag pass over a validator axis sharded on `mesh`
    (reference collective; production epoch flags ride the fused
    ops.epoch_sweep dispatch)."""
    jfn = jax.jit(jax.shard_map(
        partial(sharded_flag_set, weight=weight,
                weight_denominator=weight_denominator,
                head_flag=head_flag),
        mesh=mesh,
        in_specs=(P(AXIS), P(AXIS), P(AXIS), P(AXIS), P(), P()),
        out_specs=(P(AXIS), P(AXIS)), check_vma=False))

    def call(eff_incr, active_cur, eligible, unsl, base_per_incr, leak):
        with jax.enable_x64(True):
            return jfn(eff_incr, active_cur, eligible, unsl,
                       jnp.int64(base_per_incr), jnp.bool_(leak))
    return call


def make_flag_deltas(mesh: Mesh, weight: int, weight_denominator: int,
                     base_per_increment: int):
    """Demo-shaped wrapper over the production pass: eligible == active,
    unslashed-participating == part & active, no leak, penalties on."""
    inner = make_flag_set(mesh, weight, weight_denominator,
                          head_flag=False)

    def call(eff_incr, active, part):
        return inner(eff_incr, active, active, part & active,
                     base_per_increment, False)
    return call


def sharded_slashings(local_eff_incr, local_mask, adjusted_total,
                      total_balance, increment, electra: bool):
    """Standalone slashing-penalty sweep (bit-exact to the slashings
    lane inside ops.epoch_sweep): the correlation penalty for every
    validator whose withdrawable epoch sits at the slashing-window
    midpoint.  Penalty lanes are local; the inputs that need global
    agreement (adjusted total, total balance) are traced scalars the
    caller derives once — electra factors the increment out before the
    multiply, pre-electra divides afterwards."""
    eff64 = local_eff_incr.astype(jnp.int64)
    if electra:
        per_incr = adjusted_total // (total_balance // increment)
        pen = eff64 * per_incr
    else:
        pen = eff64 * adjusted_total // total_balance * increment
    return jnp.where(local_mask, pen, 0)


def make_slashings(mesh: Mesh, electra: bool):
    """Compiled slashing sweep over a validator axis sharded on `mesh`
    (reference collective; production slashings ride the fused
    ops.epoch_sweep dispatch)."""
    jfn = jax.jit(jax.shard_map(
        partial(sharded_slashings, electra=electra),
        mesh=mesh,
        in_specs=(P(AXIS), P(AXIS), P(), P(), P()),
        out_specs=P(AXIS), check_vma=False))

    def call(eff_incr, mask, adjusted_total, total_balance, increment):
        with jax.enable_x64(True):
            return jfn(eff_incr, mask, jnp.int64(adjusted_total),
                       jnp.int64(total_balance), jnp.int64(increment))
    return call
