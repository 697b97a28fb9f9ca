"""Algorithm 2 — truncated mini-batch kernel k-means (the paper's core).

One iteration (Theorem 1(1): O(k (tau+b)^2) kernel evaluations):

1. sample a batch B of b points uniformly with replacement (PRNG-keyed);
2. assign each batch point to the nearest truncated center
   (d(x, C_j) = K(x,x) - 2 <phi(x), C_j> + <C_j, C_j>, where
   <phi(x), C_j> = sum_w coef[j,w] K(x, X[idx[j,w]]));
3. per-center learning rate alpha_j (beta or sklearn, rates.py);
4. decay existing coefficients by (1 - alpha_j) and append the assigned
   batch points with coefficient alpha_j / b_j into the ring window;
5. refresh <C_j, C_j> (paper-faithful O(k W^2) recompute, or the
   beyond-paper O(k W b) incremental mode);
6. early stopping when the batch objective improves by less than epsilon.

Everything is fixed-shape and jit-compatible; ``make_step`` closes over the
static config and returns a pure step function.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.api import keys as api_keys
from repro.core.kernel_fns import (
    KernelFn, Precomputed, diag_of, gram_rows_fn, kernel_cross,
)
from repro.core.loop import (  # noqa: F401  (re-exported loop-core names)
    compress_hook, drive_fit_loop, precision_plan, run_early_stopped,
    run_early_stopped_keyed, scope,
)
from repro.core.rates import get_rate
from repro.core.state import CenterState, init_state, window_size


class MBConfig(NamedTuple):
    """Static configuration for Algorithm 2 (hashable -> jit static arg)."""

    k: int
    batch_size: int
    tau: int
    rate: str = "beta"              # 'beta' (paper theory) | 'sklearn'
    sqnorm_mode: str = "recompute"  # 'recompute' (paper) | 'incremental'
    eval_mode: str = "direct"       # 'direct' (paper) | 'delta' (beyond-paper)
    epsilon: float = 1e-4
    max_iters: int = 200
    use_pallas: bool = False        # fused_assign Pallas kernel for step 2
    compute_dtype: str = "float32"  # 'bfloat16': MXU-native kernel evals
    step: str = "composed"          # 'fused': streaming one-pass step
    #   (repro.kernels.fused_step; online argmin, no (b, kW) strip in HBM;
    #   bit-identical to 'composed' at f32 — see docs/perf.md)
    compress: Optional[tuple] = None  # landmark CompressSpec (hashable) —
    #   every compress.every-th iteration ends with an in-place Nystrom
    #   projection of every window onto compress.m landmark rows
    #   (repro.landmark.compress; None emits the historical program
    #   unchanged — docs/compression.md)


class StepInfo(NamedTuple):
    f_before: jax.Array     # f_B(C_i)      — batch objective at entry
    f_after: jax.Array      # f_B(C_{i+1})  — batch objective after update
    improvement: jax.Array  # f_before - f_after (early stop: < epsilon)
    batch_counts: jax.Array  # (k,) b_i^j
    assignments: jax.Array   # (b,) int32


def _batch_center_dots(kernel: KernelFn, xb: jax.Array, x: jax.Array,
                       idx: jax.Array, coef: jax.Array,
                       use_pallas: bool, cdt=None) -> jax.Array:
    """P[x, j] = <phi(x), C_j> for batch xb against windowed centers.

    ``cdt``: optional kernel-eval compute dtype for the COORDINATES (the
    ``precision="bf16"`` axis); the coefficient contraction stays f32.
    None (the default) emits the historical program unchanged."""
    k, w = idx.shape
    if use_pallas:
        from repro.kernels import ops as kops
        rows_fn = gram_rows_fn(kernel)
        if rows_fn is not None:
            # gather-from-cache path: resolve the batch's full Gram rows
            # once (hits skip kernel evals), then the Pallas kernel fuses
            # the support-column gather with the coefficient contraction —
            # zero kernel evaluations for resident rows.
            return kops.cached_assign_dots(rows_fn(kernel, xb), idx, coef)
        xbc = xb if cdt is None else xb.astype(cdt)
        sup = x[idx.reshape(-1)]
        return kops.fused_batch_center_dots(
            kernel, xbc, sup if cdt is None else sup.astype(cdt), coef)
    sup = x[idx.reshape(-1)]                      # (k*W, d)
    if cdt is not None:
        cross = kernel_cross(kernel, xb.astype(cdt), sup.astype(cdt)) \
            .astype(jnp.float32)
    else:
        cross = kernel_cross(kernel, xb, sup)     # (b, k*W)
    return jnp.einsum("bkw,kw->bk", cross.reshape(xb.shape[0], k, w), coef)


def _append_to_windows(idx, coef, head, alpha, bj, onehot, batch_idx):
    """Masked ring-buffer append.  Returns new (idx, coef, head) plus the
    (post-decay) index/coefficient of every evicted slot — the incremental
    sqnorm path needs them.  b_j <= b <= W, so within one iteration the
    write positions never collide."""
    k, w = idx.shape
    b = batch_idx.shape[0]

    def one_center(idx_row, coef_row, head_j, alpha_j, bj_j, mask_j):
        # position among this center's assigned points, for each batch slot
        pos = jnp.cumsum(mask_j.astype(jnp.int32)) - 1            # (b,)
        slot = (head_j + pos) % w
        slot = jnp.where(mask_j, slot, w)                          # w => drop
        evict_coef = coef_row.at[slot].get(mode="fill", fill_value=0.0)
        evict_idx = idx_row.at[slot].get(mode="fill", fill_value=0)
        newc = alpha_j / jnp.maximum(bj_j, 1.0)
        coef_row = coef_row.at[slot].set(newc, mode="drop")
        idx_row = idx_row.at[slot].set(batch_idx, mode="drop")
        head_new = (head_j + bj_j.astype(jnp.int32)) % w
        return idx_row, coef_row, head_new, evict_idx, evict_coef

    mask = onehot.T.astype(bool)                                   # (k, b)
    return jax.vmap(one_center)(idx, coef, head, alpha, bj, mask)


def _sqnorm_recompute(kernel, x, idx, coef, cdt=None):
    """Paper-faithful <C_j, C_j>: per-center W x W Gram quadratic form.
    Empty slots (coef 0) contribute nothing.

    Kernels advertising the ``gram_rows`` capability (cached kernels)
    resolve all k*W support rows in ONE lookup outside the vmap and gather
    the per-center W x W blocks inside it — a cached lookup placed under
    the per-center vmap would lower its ``lax.cond`` to ``select`` and run
    the miss branch (a full strip recompute) on every hit.  A
    ``Precomputed`` Gram takes :func:`_gram_sqnorm`.

    ``cdt``: optional compute dtype for the Gram COORDINATES (the fused
    step's bf16 mode); coefficients and the quadratic form stay f32."""
    if isinstance(kernel, Precomputed):
        return _gram_sqnorm(kernel, x, idx, coef)
    rows_fn = gram_rows_fn(kernel)
    if rows_fn is not None:
        k, w = idx.shape
        rows = rows_fn(kernel, x[idx.reshape(-1)])                 # (kW, n)
        rows_k = rows.reshape(k, w, rows.shape[-1])

        def one_cached(rows_j, idx_row, coef_row):
            g = rows_j[:, idx_row]                                 # (W, W)
            return coef_row @ (g.astype(jnp.float32) @ coef_row)

        return jax.vmap(one_cached)(rows_k, idx, coef)

    def one(idx_row, coef_row):
        pts = x[idx_row]                                           # (W, d)
        if cdt is not None:
            pts = pts.astype(cdt)
        g = kernel_cross(kernel, pts, pts)                         # (W, W)
        if cdt is not None:
            g = g.astype(jnp.float32)
        return coef_row @ (g @ coef_row)

    return jax.vmap(one)(idx, coef)


def _gram_ids(x, idx):
    """Gram column ids (k, W) of the windows' dataset rows: index data
    holds each row's id into the Gram."""
    return x[idx.reshape(-1), 0].astype(jnp.int32).reshape(idx.shape)


def sqnorm_route(k: int, w: int, n: int) -> str:
    """How :func:`_gram_sqnorm` reads an n x n Gram for k windows of W
    slots: ``"blocks"``, the k (W, W) blocks, while the k W support rows
    are fewer than the Gram's n; else ``"gram"``, one pass of the whole
    Gram, which then reads no more rows than the support has."""
    return "blocks" if k * w < n else "gram"


def _gram_sqnorm(kernel: Precomputed, x, idx, coef):
    """<C_j, C_j> = V_j G V_j^T from a ``Precomputed`` Gram G, every
    product at ``Precision.HIGHEST``, by :func:`sqnorm_route`:

    * ``"blocks"``: gather each center's (W, W) block of G, then
      c_j^T (G_j c_j);
    * ``"gram"``: scatter the windows into dense weights V (k, n)
      (duplicate ids add, as in the window sum), one pass of G against
      V^T — through the Gram-row kernel where it compiles natively — and
      <C_j, C_j> = sum_c V[j, c] (G V^T)[c, j]."""
    from repro.kernels import ops as kops
    from repro.kernels.cached_gather import window_weights

    hi = jax.lax.Precision.HIGHEST
    gram = kernel.gram
    k, w = idx.shape
    ids = _gram_ids(x, idx)
    if sqnorm_route(k, w, gram.shape[0]) == "blocks":
        def one(ids_j, c_j):
            g = gram[ids_j][:, ids_j]                              # (W, W)
            return jnp.dot(c_j, jnp.dot(g, c_j, precision=hi), precision=hi)

        return jax.vmap(one)(ids, coef)
    with scope("kkm.gather"):
        v = window_weights(ids, coef, gram.shape[1])               # (k, n)
    gv = (kops.gram_row_dots(gram, v) if kops.native()
          else jnp.dot(gram, v.T, precision=hi))                   # (n, k)
    return jnp.sum(v.T * gv, axis=0)


def _gram_pass(kernel: Precomputed, x, rows, idx, coef):
    """P = <phi(x_B), C_j> from the batch's Gram rows (b, n): the windows
    scattered into dense weights V (k, n), then rows @ V^T through the
    Gram-row kernel on the MXU at ``Precision.HIGHEST``
    (:mod:`repro.kernels.cached_gather`) — no (b, k*W) strip."""
    from repro.kernels import ops as kops
    from repro.kernels.cached_gather import window_weights

    with scope("kkm.gather"):
        v = window_weights(_gram_ids(x, idx), coef, rows.shape[1])
    return kops.gram_row_dots(rows, v)


def _make_fused_step(kernel: KernelFn, cfg: MBConfig):
    """The `step="fused"` Algorithm-2 iteration: both batch x window
    passes (assignment and the post-update objective) run through the
    streaming fused kernels (:mod:`repro.kernels.fused_step`) — online
    argmin carries instead of a materialized (b, k*W) cross strip or
    (b, k) distance matrix.  The O(k b) bookkeeping (rates, ring append)
    and the O(k W^2) sqnorm recompute are shared verbatim with the
    composed step, so at f32 the trajectories are BIT-IDENTICAL
    (tests/test_api_grid.py pins this across the plan grid).

    ``compute_dtype='bfloat16'`` (SolverConfig ``precision="bf16"``)
    casts kernel-eval coordinates to bf16; contractions, argmin carries
    and all state stay f32."""
    from repro.kernels import ops as kops

    if cfg.sqnorm_mode != "recompute" or cfg.eval_mode != "direct":
        raise ValueError(
            "step='fused' streams both batch x window passes, which "
            "exist only under the paper-faithful sqnorm_mode='recompute'"
            " / eval_mode='direct' (the incremental/delta variants need "
            "the materialized per-center dots the fused step never "
            "forms); use step='composed'")
    from repro.core.kernel_fns import is_index_data

    rate_fn = get_rate(cfg.rate)
    b = cfg.batch_size
    # index-data kernels (Precomputed / cached): never cast — their data
    # rows are gather KEYS, and their kernel values are cache/Gram
    # gathers, so the streaming slab loop would also just multiply
    # lookups with zero memory win.  They take the composed passes below.
    prec = precision_plan(kernel, cfg)
    index_data, precision, cdt = prec.index_data, prec.tag, prec.cdt
    # a Precomputed Gram where the kernels compile natively (TPU): both
    # passes contract the batch's Gram rows, gathered once per step, with
    # the windows' dense weights on the MXU (_gram_pass).  Elsewhere the
    # composed dots below run: the trajectory the CPU tests hold the
    # kernel to.
    gram_pass = isinstance(kernel, Precomputed) and kops.native()

    def index_dots(xb, x, rows, idx, coef):
        if gram_pass:
            return _gram_pass(kernel, x, rows, idx, coef)
        return _batch_center_dots(kernel, xb, x, idx, coef, cfg.use_pallas)

    def step(state: CenterState, x: jax.Array, batch_idx: jax.Array):
        k, w = state.idx.shape
        with scope("kkm.gather"):
            xb = x[batch_idx]                                      # (b, d)
            diag_b = diag_of(kernel, xb)                          # (b,)
            sup = None if index_data else x[state.idx.reshape(-1)]
            rows = (kernel.gram[xb[:, 0].astype(jnp.int32)]       # (b, n)
                    if gram_pass else None)

        # ---- (2) streaming assignment: online argmin over centers ---------
        with scope("kkm.assign"):
            if index_data:
                # cached/precomputed: ONE bulk row resolve (the composed
                # dots), then min/argmin — per-slab lookups would re-run
                # the cache's key scan k/kc times for values that are
                # gathers
                p = index_dots(xb, x, rows, state.idx, state.coef)
                dists = diag_b[:, None] - 2.0 * p + state.sqnorm[None, :]
                best = jnp.min(dists, axis=1)
                assign = jnp.argmin(dists, axis=1).astype(jnp.int32)
            else:
                best, assign = kops.streaming_assign(
                    kernel, xb, sup, state.coef, state.sqnorm, diag_b,
                    precision=precision)

        # ---- (3)/(4) rates + ring append: shared with the composed step ---
        with scope("kkm.update"):
            f_before = jnp.mean(best)
            onehot = jax.nn.one_hot(assign, k, dtype=jnp.float32)  # (b, k)
            bj = jnp.sum(onehot, axis=0)                           # (k,)
            alpha = rate_fn(bj, state.counts, b)                   # (k,)
            coef_scaled = state.coef * (1.0 - alpha)[:, None]
            new_idx, new_coef, new_head, _, _ = _append_to_windows(
                state.idx, coef_scaled, state.head, alpha, bj, onehot,
                batch_idx)
            counts = state.counts + bj

        # ---- (5) center squared norms (paper-faithful recompute) ----------
        # streamed center-chunked recompute: the (k, W, W) Gram stack is
        # the step's LARGEST buffer — streaming it is most of the fused
        # step's peak-memory win.  Index-data kernels keep the composed
        # bulk-lookup recompute: one row resolve beats k/kc chunked
        # resolves, and their Gram values are gathers anyway.
        with scope("kkm.sqnorm"):
            if index_data:
                new_sqnorm = _sqnorm_recompute(kernel, x, new_idx, new_coef)
            else:
                from repro.kernels.fused_step import streamed_sqnorm
                new_sqnorm = streamed_sqnorm(kernel, x, new_idx, new_coef,
                                             compute_dtype=cdt)

        # ---- (6) streaming objective on the NEW centers -------------------
        with scope("kkm.gather"):
            new_sup = None if index_data else x[new_idx.reshape(-1)]
        with scope("kkm.objective"):
            if index_data:
                p_new = index_dots(xb, x, rows, new_idx, new_coef)
                d_new = diag_b[:, None] - 2.0 * p_new + new_sqnorm[None, :]
                best2 = jnp.min(d_new, axis=1)
            else:
                best2 = kops.streaming_min(
                    kernel, xb, new_sup, new_coef, new_sqnorm, diag_b,
                    precision=precision)
            f_after = jnp.mean(best2)

        new_state = CenterState(
            idx=new_idx, coef=new_coef, head=new_head, sqnorm=new_sqnorm,
            counts=counts, step=state.step + 1)
        info = StepInfo(f_before=f_before, f_after=f_after,
                        improvement=f_before - f_after,
                        batch_counts=bj, assignments=assign)
        return new_state, info

    return step


def _maybe_compress(step, kernel: KernelFn, cfg: MBConfig):
    """The loop core's single compress-axis registration site
    (:func:`repro.core.loop.compress_hook`), applied to a CenterState
    step.  ``compress=None`` (and ``every=0``) return ``step`` itself —
    the emitted program is the historical one, bit-for-bit."""
    return compress_hook(step, kernel, cfg)


def make_step(kernel: KernelFn, cfg: MBConfig):
    """Returns step(state, x, batch_idx) -> (state, StepInfo): one Algorithm-2
    iteration.  Pure; jit/shard_map-able; x passed explicitly (never a baked
    constant).  ``cfg.step`` selects the implementation: 'composed' (the
    historical op chain below) or 'fused' (:func:`_make_fused_step` —
    streaming passes, bit-identical at f32).  An active ``cfg.compress``
    spec lands on BOTH implementations here (:func:`_maybe_compress`), so
    every CenterState executor gets in-loop compression for free."""
    if cfg.step == "fused":
        return _maybe_compress(_make_fused_step(kernel, cfg), kernel, cfg)
    if cfg.step != "composed":
        raise ValueError(f"step={cfg.step!r} (expected 'composed' or "
                         "'fused')")
    rate_fn = get_rate(cfg.rate)
    b = cfg.batch_size
    # kernel-eval compute dtype (SolverConfig precision="bf16"): resolved
    # by the loop core's single precision-axis site — cast the COORDINATES
    # entering kernel evaluations, accumulate in f32.  float32 (the
    # default) is the identity: the emitted program is unchanged.
    prec = precision_plan(kernel, cfg)
    cdt, _c, _f32 = prec.cdt, prec.cast, prec.f32

    def step(state: CenterState, x: jax.Array, batch_idx: jax.Array):
        k, w = state.idx.shape
        with scope("kkm.gather"):
            xb = x[batch_idx]                                      # (b, d)
            diag_b = diag_of(kernel, xb)                          # (b,)

        # ---- (2) assignment against current truncated centers -------------
        with scope("kkm.assign"):
            p = _batch_center_dots(kernel, xb, x, state.idx, state.coef,
                                   cfg.use_pallas, cdt=cdt)        # (b, k)
            dists = diag_b[:, None] - 2.0 * p + state.sqnorm[None, :]
            assign = jnp.argmin(dists, axis=1).astype(jnp.int32)

        with scope("kkm.update"):
            f_before = jnp.mean(jnp.min(dists, axis=1))
            onehot = jax.nn.one_hot(assign, k, dtype=jnp.float32)  # (b, k)
            bj = jnp.sum(onehot, axis=0)                           # (k,)

            # ---- (3) learning rate ----------------------------------------
            alpha = rate_fn(bj, state.counts, b)                   # (k,)
            decay = 1.0 - alpha

            # ---- (4) decay + ring append ----------------------------------
            coef_scaled = state.coef * decay[:, None]
            new_idx, new_coef, new_head, evict_idx, evict_coef = \
                _append_to_windows(state.idx, coef_scaled, state.head,
                                   alpha, bj, onehot, batch_idx)
            counts = state.counts + bj
            onehot_n = onehot / jnp.maximum(bj, 1.0)[None, :]      # (b, k)

        # ---- (5) center squared norms --------------------------------------
        with scope("kkm.sqnorm"):
            if cfg.sqnorm_mode == "recompute":
                new_sqnorm = _sqnorm_recompute(kernel, x, new_idx, new_coef,
                                               cdt=cdt)
                kbb = None
            elif cfg.sqnorm_mode == "incremental":
                # <C', C'> for the *untruncated* update, then subtract the
                # evicted component D:
                # <C-D, C-D> = <C,C> - 2<C-D, D> - <D,D>.
                kbb = _f32(kernel_cross(kernel, _c(xb), _c(xb)))   # (b, b)
                cm_cross = jnp.sum(onehot * p, axis=0) / \
                    jnp.maximum(bj, 1.0)
                cm_sq = jnp.sum(onehot_n * (kbb @ onehot_n), axis=0)
                sq_untrunc = (decay ** 2 * state.sqnorm
                              + 2.0 * decay * alpha * cm_cross
                              + alpha ** 2 * cm_sq)

                def corr(evict_i, evict_c, idx_row, coef_row):
                    kd_w = _f32(kernel_cross(kernel, _c(x[evict_i]),
                                             _c(x[idx_row])))        # (b, W)
                    c_d_new = evict_c @ (kd_w @ coef_row)  # <D, C_trunc>
                    kdd = _f32(kernel_cross(kernel, _c(x[evict_i]),
                                            _c(x[evict_i])))
                    dd = evict_c @ (kdd @ evict_c)         # <D, D>
                    return 2.0 * c_d_new + dd

                new_sqnorm = sq_untrunc - jax.vmap(corr)(
                    evict_idx, evict_coef, new_idx, new_coef)
            else:
                raise ValueError(cfg.sqnorm_mode)

        # ---- (6) batch objective on the NEW centers (early stopping) ------
        with scope("kkm.objective"):
            if cfg.eval_mode == "direct":
                p_new = _batch_center_dots(kernel, xb, x, new_idx, new_coef,
                                           cfg.use_pallas, cdt=cdt)
            elif cfg.eval_mode == "delta":
                # <phi(x), C'_j> = decay_j P[x,j] + alpha_j <phi(x), cm(B_j)>
                #                  - <phi(x), D_j>     — O(k b^2), no kW pass
                if kbb is None:
                    kbb = _f32(kernel_cross(kernel, _c(xb), _c(xb)))
                cm_dot = kbb @ onehot_n                            # (b, k)

                def drop_dot(evict_i, evict_c):
                    return _f32(kernel_cross(kernel, _c(xb),
                                             _c(x[evict_i]))) @ evict_c

                d_dot = jax.vmap(drop_dot)(evict_idx, evict_coef).T
                p_new = decay[None, :] * p + alpha[None, :] * cm_dot - d_dot
            else:
                raise ValueError(cfg.eval_mode)

            d_new = diag_b[:, None] - 2.0 * p_new + new_sqnorm[None, :]
            f_after = jnp.mean(jnp.min(d_new, axis=1))

        new_state = CenterState(
            idx=new_idx, coef=new_coef, head=new_head, sqnorm=new_sqnorm,
            counts=counts, step=state.step + 1)
        info = StepInfo(f_before=f_before, f_after=f_after,
                        improvement=f_before - f_after,
                        batch_counts=bj, assignments=assign)
        return new_state, info

    return _maybe_compress(step, kernel, cfg)


def batch_objective(kernel: KernelFn, state: CenterState, x: jax.Array,
                    batch_idx: jax.Array,
                    use_pallas: bool = False) -> jax.Array:
    """f_B(C) = mean_j min_j d(x, C_j) on an explicit batch — the quantity
    Algorithm 2 early-stops on, exposed standalone so the multi-restart
    engine can score every restart's centers on one SHARED eval batch
    (fair on-device model selection, no host sync).  vmap-safe over state."""
    xb = x[batch_idx]
    diag_b = diag_of(kernel, xb)
    p = _batch_center_dots(kernel, xb, x, state.idx, state.coef, use_pallas)
    dists = diag_b[:, None] - 2.0 * p + state.sqnorm[None, :]
    return jnp.mean(jnp.min(dists, axis=1))


def batch_objective_from_rows(gram_rows: jax.Array, diag_b: jax.Array,
                              state: CenterState) -> jax.Array:
    """``batch_objective`` from precomputed Gram rows K(x_B, x) (eb, n):
    the cross-kernel block against each center's support window becomes a
    column gather, so R restarts scored on one shared eval batch pay the
    eb x n kernel evaluations ONCE instead of R times (engine.py).
    vmap-safe over state."""
    k, w = state.idx.shape
    cross = gram_rows[:, state.idx.reshape(-1)]            # (eb, k*W)
    p = jnp.einsum("bkw,kw->bk", cross.reshape(gram_rows.shape[0], k, w),
                   state.coef)
    dists = diag_b[:, None] - 2.0 * p + state.sqnorm[None, :]
    return jnp.mean(jnp.min(dists, axis=1))


def sample_batch(key: jax.Array, n: int, b: int) -> jax.Array:
    """Uniform with replacement (paper's sampling model)."""
    return jax.random.randint(key, (b,), 0, n, dtype=jnp.int32)


def sample_batch_weighted(key: jax.Array, probs: jax.Array,
                          b: int) -> jax.Array:
    """Weighted case (paper footnote 1): sampling x with probability
    proportional to w_x makes the plain batch mean an unbiased estimator of
    the weighted objective and the plain cm(B_j) the weighted center update
    — Algorithm 2 itself is unchanged."""
    return jax.random.choice(key, probs.shape[0], (b,), p=probs) \
        .astype(jnp.int32)


def sample_batch_nested(key: jax.Array, step, n: int, b: int,
                        reuse: float = 0.5,
                        refresh: int = 8) -> jax.Array:
    """Nested batch sampling (Newling & Fleuret 2016 style reuse): the
    first ``reuse * b`` positions form a slowly-refreshing prefix — position
    ``i`` keeps its row for ``refresh`` steps (staggered, so ~m/refresh
    rows turn over per step) — and the tail is drawn fresh each step.

    Consecutive batches therefore share most of their rows, which is what
    keeps the Gram tile cache's hit rate high during fit.  Marginally each
    position is still uniform over [0, n).  Pure function of ``(key, step)``
    like :func:`sample_batch` — deterministic resume needs no sampler
    state."""
    m = int(b * reuse)
    step = jnp.asarray(step, jnp.int32)
    if m > 0:
        i = jnp.arange(m, dtype=jnp.int32)
        epoch = (step + i) // refresh

        def draw(ii, ee):
            kk = jax.random.fold_in(jax.random.fold_in(key, ii), ee)
            return jax.random.randint(kk, (), 0, n, dtype=jnp.int32)

        head = jax.vmap(draw)(i, epoch)
    else:
        head = jnp.zeros((0,), jnp.int32)
    kt = jax.random.fold_in(jax.random.fold_in(key, step), 0x7A11)
    tail = jax.random.randint(kt, (b - m,), 0, n, dtype=jnp.int32)
    return jnp.concatenate([head, tail])


def host_fit_loop(step, n: int, cfg: MBConfig, state, key: jax.Array,
                  probs: Optional[jax.Array] = None,
                  early_stop: bool = True, sampler: str = "iid",
                  reuse: float = 0.5, refresh: int = 8, step0: int = 0,
                  prefetch: bool = False):
    """The host-driven early-stopped driver shared by every non-jit fit
    path (plain / weighted / cached): per iteration draw the batch indices
    from the unified key stream (:mod:`repro.api.keys`), apply
    ``step(state, batch_idx) -> (state, StepInfo)``, and stop when the
    improvement drops below epsilon.

    ``sampler='iid'`` advances the stream (``next_batch_key``) each step;
    ``'nested'`` batches are pure functions of ``(key, step)`` and leave
    the stream untouched.  ``step0`` offsets the iteration counter so
    ``partial_fit`` resumption continues both the nested schedule and the
    history numbering.  Returns ``(state, history, key)`` — the carried key
    resumes the stream exactly (``KernelKMeans.partial_fit``).

    ``prefetch``: one-deep pipeline — draw (and ``device_put``) iteration
    i+1's batch indices after DISPATCHING step i but before blocking on
    its improvement, so sampling/transfer overlaps the device step.  The
    drawn values, the visited key stream and the returned carry key are
    identical to the blocking path (an early stop discards the prefetched
    draw without consuming its key advance) — results are bit-identical
    either way (tested).

    This is a thin lowering over the shared host driver
    (:func:`repro.core.loop.drive_fit_loop`): it supplies only the
    key-stream batch producer and the step dispatch; the loop skeleton
    (iteration/early-stop/prefetch/history) lives in the loop core."""
    if sampler not in ("iid", "nested"):
        raise ValueError(sampler)
    if sampler == "nested" and probs is not None:
        raise NotImplementedError("the nested sampler draws unweighted "
                                  "batches; sample weights need "
                                  "sampler='iid'")

    def draw(key, i):
        """-> (key', bidx): one batch draw at cursor i.  'nested' draws
        are pure functions of (key, i) and leave the stream untouched."""
        if sampler == "iid":
            key, kb = api_keys.next_batch_key(key)
            return key, (sample_batch(kb, n, cfg.batch_size)
                         if probs is None
                         else sample_batch_weighted(kb, probs,
                                                    cfg.batch_size))
        return key, sample_batch_nested(key, i, n, cfg.batch_size,
                                        reuse=reuse, refresh=refresh)

    def dispatch(bidx):
        nonlocal state
        state, info = step(state, bidx)
        return info

    history, key = drive_fit_loop(
        dispatch, draw, key, max_iters=cfg.max_iters, epsilon=cfg.epsilon,
        early_stop=early_stop, prefetch=prefetch, step0=step0)
    return state, history, key


def fit(x: jax.Array, kernel: KernelFn, cfg: MBConfig, key: jax.Array,
        init: str = "kmeans++", early_stop: bool = True,
        init_idx: Optional[jax.Array] = None,
        weights: Optional[jax.Array] = None):
    """Host-driven fit loop with the paper's early-stopping condition.

    .. deprecated::
        Use :class:`repro.api.KernelKMeans` with
        ``SolverConfig(cache="none", distribution="single", jit=False)`` —
        this shim resolves exactly that plan and delegates to it.

    ``weights``: optional (n,) positive point weights (footnote 1) —
    implemented as weighted batch sampling, see sample_batch_weighted.
    Returns (state, history) where history is a list of per-step StepInfo
    (as numpy scalars) — benchmarks consume it directly.
    """
    from repro.api import legacy as _legacy
    _legacy.warn_legacy(
        "repro.core.fit",
        "KernelKMeans(SolverConfig(cache='none', distribution='single', "
        "jit=False))")
    return _legacy.fit(x, kernel, cfg, key, init=init,
                       early_stop=early_stop, init_idx=init_idx,
                       weights=weights)


def fit_cached(x: jax.Array, kernel: KernelFn, cfg: MBConfig, key: jax.Array,
               tile: int = 256, capacity: int = 16,
               init: str = "kmeans++", early_stop: bool = True,
               init_idx: Optional[jax.Array] = None,
               sampler: str = "uniform", reuse: float = 0.5,
               refresh: int = 8, store_dtype=jnp.float32):
    """Cache-accelerated host-driven fit (the Gram-tile-cache fit path).

    .. deprecated::
        Use :class:`repro.api.KernelKMeans` with
        ``SolverConfig(cache="lru", sampler="iid"|"nested")`` — this shim
        resolves exactly that plan and delegates to it.

    Per iteration: warm the tile cache with the batch + window rows (only
    MISSING row blocks evaluate the kernel; the nested sampler keeps that
    set small), then run the unchanged Algorithm-2 step on the index-data
    view — every ``kernel_cross`` inside it is served from resident tiles.

    ``sampler='uniform'`` draws the exact batch sequence of :func:`fit`
    (same key handling), so cached and uncached fits are numerically
    equivalent; ``sampler='nested'`` uses :func:`sample_batch_nested` for
    higher hit rates.  Returns ``(state, history, ck)`` — the returned
    :class:`repro.cache.CachedKernel` carries the warm tiles plus measured
    hit/miss/eviction counters, and serves ``predict`` /
    ``predict_cached`` directly.
    """
    from repro.api import legacy as _legacy
    _legacy.warn_legacy(
        "repro.core.fit_cached",
        "KernelKMeans(SolverConfig(cache='lru'))")
    return _legacy.fit_cached(x, kernel, cfg, key, tile=tile,
                              capacity=capacity, init=init,
                              early_stop=early_stop, init_idx=init_idx,
                              sampler=sampler, reuse=reuse, refresh=refresh,
                              store_dtype=store_dtype)


# run_early_stopped_keyed / run_early_stopped — the paper's on-device
# early-stopped driver — moved to repro.core.loop (re-exported above): the
# lax.while_loop skeleton now exists exactly once, in the loop core.


def sampled_step_with_key(step, x: jax.Array, cfg: MBConfig):
    """Adapt make_step's (state, x, batch_idx) signature to the
    run_early_stopped protocol with the canonical uniform batch draw."""
    n = x.shape[0]

    def step_with_key(state, kb):
        with scope("kkm.sample"):
            batch_idx = sample_batch(kb, n, cfg.batch_size)
        state, info = step(state, x, batch_idx)
        return state, info.improvement

    return step_with_key


def fit_jit(x: jax.Array, kernel: KernelFn, cfg: MBConfig, key: jax.Array,
            init_idx: jax.Array):
    """Fully-on-device fit: lax.while_loop with the stopping condition in the
    loop — no per-step host sync (the production/TPU path).

    .. deprecated::
        Use :class:`repro.api.KernelKMeans` with ``SolverConfig(jit=True)``
        — this shim resolves exactly that plan and delegates to it (the
        estimator additionally caches the compiled program across fits).
    """
    from repro.api import legacy as _legacy
    _legacy.warn_legacy(
        "repro.core.fit_jit",
        "KernelKMeans(SolverConfig(cache='none', distribution='single', "
        "jit=True))")
    return _legacy.fit_jit(x, kernel, cfg, key, init_idx)


def assign_chunked(kernel: KernelFn, coef: jax.Array, sqnorm: jax.Array,
                   sup: jax.Array, xq: jax.Array, chunk: int) -> jax.Array:
    """Chunked nearest-center assignment against explicit (k*W, d) support
    points — the single serving kernel, shared by ``predict`` and the
    sharded ``distributed.predict_distributed`` body so their numerics can
    never diverge.

    Support-side invariants (the (k*W,) support squared norms of the
    Gaussian) are hoisted OUT of the chunk scan via
    :func:`repro.core.kernel_fns.cross_fixed_y` — they are fixed across
    every chunk, and recomputing them per chunk cost O(kWd) per chunk for
    nothing; the query side already uses the :func:`diag_of`
    normalized-kernel fast path.  Hoisting reuses the same ops on the same
    data, so labels are unchanged bit-for-bit."""
    from repro.core.kernel_fns import cross_fixed_y

    k, w = coef.shape
    cross_fn = cross_fixed_y(kernel, sup)     # sup stats computed ONCE

    def one_chunk(xc):
        cross = cross_fn(xc).reshape(xc.shape[0], k, w)
        p = jnp.einsum("bkw,kw->bk", cross, coef)
        d = diag_of(kernel, xc)[:, None] - 2.0 * p + sqnorm[None, :]
        return jnp.argmin(d, axis=1).astype(jnp.int32)

    nq = xq.shape[0]
    pad = (-nq) % chunk
    xp = jnp.pad(xq, ((0, pad),) + ((0, 0),) * (xq.ndim - 1))
    out = jax.lax.map(one_chunk, xp.reshape(-1, chunk, *xq.shape[1:]))
    return out.reshape(-1)[:nq]


def center_distances_chunked(kernel: KernelFn, coef: jax.Array,
                             sqnorm: jax.Array, sup: jax.Array,
                             xq: jax.Array, chunk: int) -> jax.Array:
    """Chunked feature-space distances d(x, C_j) against explicit (k*W, d)
    support points, (nq, k) — the ``KernelKMeans.transform`` / ``score``
    kernel.  Same distance expression as :func:`assign_chunked` (which only
    keeps the argmin), with the same support-invariant hoist."""
    from repro.core.kernel_fns import cross_fixed_y

    k, w = coef.shape
    cross_fn = cross_fixed_y(kernel, sup)     # sup stats computed ONCE

    def one_chunk(xc):
        cross = cross_fn(xc).reshape(xc.shape[0], k, w)
        p = jnp.einsum("bkw,kw->bk", cross, coef)
        return diag_of(kernel, xc)[:, None] - 2.0 * p + sqnorm[None, :]

    nq = xq.shape[0]
    pad = (-nq) % chunk
    xp = jnp.pad(xq, ((0, pad),) + ((0, 0),) * (xq.ndim - 1))
    out = jax.lax.map(one_chunk, xp.reshape(-1, chunk, *xq.shape[1:]))
    return out.reshape(-1, k)[:nq]


@functools.partial(jax.jit, static_argnames=("chunk",))
def predict(state: CenterState, x: jax.Array, xq: jax.Array,
            kernel: KernelFn, chunk: int = 4096) -> jax.Array:
    """Assign arbitrary points to the fitted (truncated) centers."""
    sup = x[state.idx.reshape(-1)]
    return assign_chunked(kernel, state.coef, state.sqnorm, sup, xq, chunk)
