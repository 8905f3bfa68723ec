"""Pallas TPU WeakHash routing kernel (integer outputs; differentiable
combine weights are reconstructed outside from the router probabilities).

Single fused ``pallas_call`` over a ``(2, nt)`` phase-major grid (TPU grids
iterate the last dimension fastest, so all of phase 0 runs before phase 1):

  phase 0 (demand): group-masked argmax histogram over all token tiles,
     accumulated into an (E,) VMEM scratch — the load estimate. The final
     demand never round-trips through HBM between phases (the pre-fusion
     version ran two kernels and re-read the (E,) demand from HBM on every
     select tile); it is exported once as an output for the API.
  phase 1 (select): demand-penalized scores → iterative top-k → arrival-
     order slot positions via an (E,) running-count scratch that carries
     across the sequential token-tile grid (matching the oracle's
     column-major cumsum exactly). The per-selection prefix cumsum is
     HOISTED out of the top-k loop: the k onehot matrices are stacked
     (k·bt, E) column-major and one cumsum produces every position.

When the whole token axis fits one tile (nt == 1) both phases run on a
single resident block, so the logits are read from HBM exactly once.

For nt > 1 the exact global-demand semantics force a second read of the
logits (phase 1 revisits every tile). The optional demand
"carry-forward" variant (`_carry_kernel`, ``carry_forward=True``) drops
to ONE pass: the penalty uses the previous batch's demand plus a running
histogram of already-processed tiles instead of the exact whole-batch
histogram — bit-identical to exact when nt == 1, an approximation
otherwise whose routing-quality delta is its load CV against the exact
kernel's.

VPU-only (no MXU); token tiles are 8×128-aligned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.flash_attention.kernel import pltpu_scratch

NEG_INF = -1e30
DEFAULT_BLOCK_T = 256
KNUTH = 2654435761


def _group_mask(keys, n_groups, E, gsz):
    """(bt, E) bool mask of each token's candidate group."""
    hashed = keys.astype(jnp.uint32) * jnp.uint32(KNUTH)
    gid = (hashed % jnp.uint32(n_groups)).astype(jnp.int32)     # (bt,)
    eg = jax.lax.broadcasted_iota(jnp.int32, (keys.shape[0], E), 1) // gsz
    return eg == gid[:, None], gid


def _fused_kernel(logits_ref, keys_ref, idx_ref, pos_ref, gid_ref, dem_ref,
                  dem_scr, count_scr, *, top_k, capacity, n_groups, E, gsz,
                  nt, load_penalty, mode, use_groups):
    phase = pl.program_id(0)
    t = pl.program_id(1)

    @pl.when(jnp.logical_and(phase == 0, t == 0))
    def _init():
        dem_scr[...] = jnp.zeros_like(dem_scr)
        count_scr[...] = jnp.zeros_like(count_scr)

    logits = logits_ref[...].astype(jnp.float32)                # (bt, E)
    bt = logits.shape[0]
    if use_groups:
        mask, gid = _group_mask(keys_ref[...], n_groups, E, gsz)
        masked = jnp.where(mask, logits, NEG_INF)
    else:
        masked = logits
        gid = jnp.zeros((bt,), jnp.int32)
    gid_ref[...] = gid
    eye = jax.lax.broadcasted_iota(jnp.int32, (bt, E), 1)

    @pl.when(phase == 0)
    def _demand():
        top1 = jnp.argmax(masked, axis=-1)                      # (bt,)
        onehot = (top1[:, None] == eye)
        dem_scr[...] += jnp.sum(onehot.astype(jnp.float32), axis=0)
        # deterministic phase-0 writeback for the revisited output tiles
        idx_ref[...] = jnp.zeros_like(idx_ref)
        pos_ref[...] = jnp.zeros_like(pos_ref)

        @pl.when(t == nt - 1)
        def _export():
            dem_ref[...] = dem_scr[...]

    @pl.when(phase == 1)
    def _select():
        if mode == "weakhash":
            scores = masked - load_penalty * (dem_scr[...][None, :]
                                              / float(max(capacity, 1)))
        else:
            scores = masked

        counts = count_scr[...]                                 # (E,) f32
        sel = scores
        onehots = []
        for j in range(top_k):
            e_j = jnp.argmax(sel, axis=-1).astype(jnp.int32)    # (bt,)
            idx_ref[:, j] = e_j
            onehots.append((eye == e_j[:, None]).astype(jnp.float32))
            sel = jnp.where(eye == e_j[:, None], NEG_INF, sel)
        # positions: ONE column-major cumsum over the stacked selections
        # replaces the per-j cumsum the loop used to carry (row (j, t) sees
        # every selection of earlier columns plus earlier tokens of its own
        # column — exactly the reference's arrival order)
        stacked = jnp.concatenate(onehots, axis=0)              # (k·bt, E)
        prefix = jnp.cumsum(stacked, axis=0) - stacked
        pos_flat = jnp.sum((counts[None, :] + prefix) * stacked, axis=-1)
        for j in range(top_k):
            pos_ref[:, j] = pos_flat[j * bt:(j + 1) * bt].astype(jnp.int32)
        count_scr[...] = counts + jnp.sum(stacked, axis=0)


def _carry_kernel(logits_ref, keys_ref, prior_ref, idx_ref, pos_ref,
                  gid_ref, dem_ref, dem_scr, count_scr, *, top_k, capacity,
                  n_groups, E, gsz, nt, load_penalty, mode, use_groups):
    """Single-pass demand "carry-forward" variant (grid ``(nt,)``).

    Exact mode needs two passes because every token's penalty uses the
    FULL batch's demand histogram. Carry-forward replaces that global
    estimate with ``prior_ref`` (the previous batch's demand — the
    streaming load signal) plus the running histogram of tiles already
    processed, so each logits tile is read from HBM exactly once even
    for nt > 1. With one tile (nt == 1) the running histogram IS the
    full batch histogram, so carry-forward with a zero prior reproduces
    the exact kernel bit-for-bit — the parity anchor in
    tests/test_kernels.py. Its quality cost is the routing load CV
    against the exact kernel's.
    """
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        dem_scr[...] = prior_ref[...]
        count_scr[...] = jnp.zeros_like(count_scr)

    logits = logits_ref[...].astype(jnp.float32)                # (bt, E)
    bt = logits.shape[0]
    if use_groups:
        mask, gid = _group_mask(keys_ref[...], n_groups, E, gsz)
        masked = jnp.where(mask, logits, NEG_INF)
    else:
        masked = logits
        gid = jnp.zeros((bt,), jnp.int32)
    gid_ref[...] = gid
    eye = jax.lax.broadcasted_iota(jnp.int32, (bt, E), 1)

    # the tile's own top-1 histogram joins the load estimate BEFORE its
    # selection (exact mode also counts a token's own batch in demand0)
    top1 = jnp.argmax(masked, axis=-1)
    dem_scr[...] += jnp.sum((top1[:, None] == eye).astype(jnp.float32),
                            axis=0)
    if mode == "weakhash":
        scores = masked - load_penalty * (dem_scr[...][None, :]
                                          / float(max(capacity, 1)))
    else:
        scores = masked

    counts = count_scr[...]                                     # (E,) f32
    sel = scores
    onehots = []
    for j in range(top_k):
        e_j = jnp.argmax(sel, axis=-1).astype(jnp.int32)        # (bt,)
        idx_ref[:, j] = e_j
        onehots.append((eye == e_j[:, None]).astype(jnp.float32))
        sel = jnp.where(eye == e_j[:, None], NEG_INF, sel)
    stacked = jnp.concatenate(onehots, axis=0)                  # (k·bt, E)
    prefix = jnp.cumsum(stacked, axis=0) - stacked
    pos_flat = jnp.sum((counts[None, :] + prefix) * stacked, axis=-1)
    for j in range(top_k):
        pos_ref[:, j] = pos_flat[j * bt:(j + 1) * bt].astype(jnp.int32)
    count_scr[...] = counts + jnp.sum(stacked, axis=0)

    @pl.when(t == nt - 1)
    def _export():
        # the batch's OWN top-1 histogram (same statistic exact mode
        # exports) — chain it into the next batch's prior_demand
        dem_ref[...] = dem_scr[...] - prior_ref[...]


def weakhash_route_ints(logits, *, top_k, capacity, n_groups=1,
                        mode="weakhash", token_keys=None, load_penalty=1.0,
                        block_t=DEFAULT_BLOCK_T, interpret=False,
                        carry_forward=False, prior_demand=None):
    """Integer routing outputs: (expert_idx, position, group_id, demand).

    ``carry_forward=True`` selects the truly single-pass variant for
    nt > 1: the demand penalty uses ``prior_demand`` (previous batch's
    histogram, zeros when None) plus the running histogram of earlier
    tiles instead of the exact whole-batch histogram (see
    `_carry_kernel`); the returned demand stays the batch's own top-1
    histogram so callers can chain batches.

    NOTE: the oracle's per-(token,k)-flattened arrival order is token-major
    with all k selections of token t adjacent; this kernel assigns positions
    per selection column j across the tile instead. Both are valid
    arrival orders; for exact oracle parity the wrapper recomputes positions
    when cross-validating — see ops.weakhash_route.
    """
    T, E = logits.shape
    bt = min(block_t, T)
    assert T % bt == 0
    nt = T // bt
    gsz = E // max(n_groups, 1)
    keys = (token_keys if token_keys is not None
            else jnp.zeros((T,), jnp.int32))
    use_groups = mode == "weakhash" and n_groups > 1

    if carry_forward:
        prior = (jnp.zeros((E,), jnp.float32) if prior_demand is None
                 else prior_demand.astype(jnp.float32))
        return pl.pallas_call(
            functools.partial(_carry_kernel, top_k=top_k,
                              capacity=capacity, n_groups=n_groups, E=E,
                              gsz=gsz, nt=nt, load_penalty=load_penalty,
                              mode=mode, use_groups=use_groups),
            grid=(nt,),
            in_specs=[pl.BlockSpec((bt, E), lambda t: (t, 0)),
                      pl.BlockSpec((bt,), lambda t: (t,)),
                      pl.BlockSpec((E,), lambda t: (0,))],
            out_specs=[pl.BlockSpec((bt, top_k), lambda t: (t, 0)),
                       pl.BlockSpec((bt, top_k), lambda t: (t, 0)),
                       pl.BlockSpec((bt,), lambda t: (t,)),
                       pl.BlockSpec((E,), lambda t: (0,))],
            out_shape=[jax.ShapeDtypeStruct((T, top_k), jnp.int32),
                       jax.ShapeDtypeStruct((T, top_k), jnp.int32),
                       jax.ShapeDtypeStruct((T,), jnp.int32),
                       jax.ShapeDtypeStruct((E,), jnp.float32)],
            scratch_shapes=[pltpu_scratch((E,), jnp.float32),
                            pltpu_scratch((E,), jnp.float32)],
            interpret=interpret,
        )(logits.astype(jnp.float32), keys.astype(jnp.int32), prior)

    idx, pos, gid, demand = pl.pallas_call(
        functools.partial(_fused_kernel, top_k=top_k, capacity=capacity,
                          n_groups=n_groups, E=E, gsz=gsz, nt=nt,
                          load_penalty=load_penalty, mode=mode,
                          use_groups=use_groups),
        grid=(2, nt),
        in_specs=[pl.BlockSpec((bt, E), lambda p, t: (t, 0)),
                  pl.BlockSpec((bt,), lambda p, t: (t,))],
        out_specs=[pl.BlockSpec((bt, top_k), lambda p, t: (t, 0)),
                   pl.BlockSpec((bt, top_k), lambda p, t: (t, 0)),
                   pl.BlockSpec((bt,), lambda p, t: (t,)),
                   pl.BlockSpec((E,), lambda p, t: (0,))],
        out_shape=[jax.ShapeDtypeStruct((T, top_k), jnp.int32),
                   jax.ShapeDtypeStruct((T, top_k), jnp.int32),
                   jax.ShapeDtypeStruct((T,), jnp.int32),
                   jax.ShapeDtypeStruct((E,), jnp.float32)],
        scratch_shapes=[pltpu_scratch((E,), jnp.float32),
                        pltpu_scratch((E,), jnp.float32)],
        interpret=interpret,
    )(logits.astype(jnp.float32), keys.astype(jnp.int32))
    return idx, pos, gid, demand


def weakhash_route(logits, *, top_k, capacity, n_groups=1, mode="weakhash",
                   token_keys=None, prior_load=None, load_penalty=1.0,
                   rescue=False, interpret=False, carry_forward=False):
    """Kernel-backed RouteResult; rescue (γ=full second pass) falls back
    to the oracle (cold path). ``carry_forward=True`` runs the
    single-pass kernel with ``prior_load`` as the previous batch's
    demand (the streaming chain signal)."""
    from repro.kernels.weakhash_route import ref
    if rescue or (prior_load is not None and not carry_forward):
        return ref.weakhash_route(
            logits, top_k=top_k, capacity=capacity, n_groups=n_groups,
            mode=mode, token_keys=token_keys, prior_load=prior_load,
            load_penalty=load_penalty, rescue=rescue)
    idx, _, gid, demand = weakhash_route_ints(
        logits, top_k=top_k, capacity=capacity, n_groups=n_groups, mode=mode,
        token_keys=token_keys, load_penalty=load_penalty,
        interpret=interpret, carry_forward=carry_forward,
        prior_demand=prior_load)
    # positions in oracle token-major order (cheap; keeps dispatch parity)
    position = ref._positions_in_expert(idx, logits.shape[1])
    keep = position < capacity
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gates = jnp.take_along_axis(probs, idx, axis=1)
    weights = gates / jnp.clip(gates.sum(-1, keepdims=True), 1e-9)
    me = probs.mean(0)
    top1 = jax.nn.one_hot(jnp.argmax(logits, -1), logits.shape[1],
                          dtype=jnp.float32).mean(0)
    aux = logits.shape[1] * jnp.sum(me * top1)
    dem = jax.nn.one_hot(idx.reshape(-1), logits.shape[1],
                         dtype=jnp.float32).sum(0)
    return ref.RouteResult(expert_idx=idx, weights=weights, position=position,
                           keep=keep, group_id=gid, demand=dem, aux_loss=aux)
