"""Mixture-of-Experts feed-forward block, GShard/Switch style.

Capability counterpart of the reference's MoE stack
(realhf/impl/model/modules/moe/{experts,router,grouped GEMM} and the
Megatron EP path, areal/engine/megatron_engine.py:451-535;
alloc grammar e/etp dims, areal/api/alloc_mode.py:80-117).  TPU-first
design:

- **Two dispatch implementations** behind one `moe_ffn` entry point:
  "capacity" uses dense dispatch/combine tensors ([tokens, E, C] one-hot)
  so routing becomes three einsums that XLA tiles straight onto the MXU —
  replacing the reference's grouped-GEMM CUDA kernels and permutation
  indices, with capacity C bounding each expert's work; "dropless" sorts
  assignments by expert and runs `lax.ragged_dot` grouped GEMMs (the
  MegaBlocks shape), reproducing HF Mixtral/Qwen3-MoE exactly — loaded
  checkpoints default to it (model_config.from_hf_dict).
- Expert weights live as [E, D, F] leaves sharded over the mesh's `ep`
  axis (partition specs in transformer.param_partition_specs); the
  dispatch einsum's contraction over tokens is what GSPMD turns into the
  all-to-all the reference drives through NCCL EP groups.
- Top-k routing, the gates renormalised when the config says so
  (`norm_topk_prob`; mixtral always), plus the
  Switch-style load-balancing auxiliary loss E * sum(f_i * P_i), threaded
  functionally through the layer scan (no global state).
- `latent_moe_ffn`: the expert block of the `nemotron_h` family — a
  sigmoid router with a selection bias, un-gated squared-ReLU experts in a
  latent space, one shared expert — told WHICH experts it holds
  (`TransformerConfig.experts_held`): it routes over all of them and
  computes its own experts' part of the result.
- `gated_moe_ffn`: the expert block of the `afmoe` family, gated experts
  at the model's width, with a backward pass.  The two expert layers at a
  share have the router and the sort in common (`route_sigmoid`,
  `held_dispatch`, `group_sizes`) and nothing else: `latent_moe_ffn` keeps
  `rows_by_expert` / `rows_by_choice` over all of a decode pass's few
  thousand assignments; `gated_moe_ffn`'s routed part (`routed_experts`)
  gathers, multiplies, activates and sums by token only the leading rows
  of the sort, a block of a static size at a time in a loop that runs
  while held rows are left (one trip, as a rule), and says how many rows
  its buffers held (`expert_rows_buffered`).
"""

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from areal_tpu.models.model_config import TransformerConfig

Params = Dict[str, jax.Array]


def expert_capacity(
    n_tokens: int, num_experts: int, top_k: int, capacity_factor: float = 1.25
) -> int:
    """Static per-expert token budget; multiples of 8 for TPU tiling."""
    c = int(n_tokens * top_k / num_experts * capacity_factor) + 1
    return max(8, (c + 7) // 8 * 8)


def _route(lp: Params, x: jax.Array, k: int, renormalise: bool = True):
    """Shared top-k router: -> (probs [N, E] fp32, gate_vals [N, k],
    gate_idx [N, k]).  `renormalise` divides the chosen gates by their sum
    (`norm_topk_prob`: mixtral always, qwen-moe as its config says)."""
    router_logits = jnp.einsum(
        "nd,de->ne", x.astype(jnp.float32), lp["router"].astype(jnp.float32)
    )
    probs = jax.nn.softmax(router_logits, axis=-1)  # [N, E] fp32
    gate_vals, gate_idx = jax.lax.top_k(probs, k)  # [N, k]
    if renormalise:
        gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)
    return probs, gate_vals, gate_idx


def route_sigmoid(
    cfg: TransformerConfig, lp: Params, x: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """The sigmoid router (nemotron_h; DeepSeek-V3's): scores s =
    sigmoid(W_r x) in float32, the top k of s + the selection bias chosen,
    weighted by s alone, over the chosen's sum if `norm_topk_prob`, times
    `routed_scaling_factor`.  x [N, D] -> (weights [N, k] f32, idx [N, k])."""
    return _route_biased(cfg, lp, x, jax.nn.sigmoid)


def route_softmax_bias(
    cfg: TransformerConfig, lp: Params, x: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """The softmax router with a selection bias (longcat_flash): scores p =
    softmax(W_r x) in float32 over ALL the router's outputs (the routed
    experts and, behind them, the identity experts), the top k of p + the
    bias chosen, weighted by p alone (not renormalised unless
    `norm_topk_prob`), times `routed_scaling_factor`."""
    return _route_biased(cfg, lp, x, jax.nn.softmax)


def _route_biased(cfg: TransformerConfig, lp: Params, x: jax.Array, score):
    f32 = jnp.float32
    scores = score(jnp.einsum(
        "nd,de->ne", x.astype(f32), lp["router"].astype(f32),
        precision=jax.lax.Precision.HIGHEST,
    ))
    _, idx = jax.lax.top_k(
        scores + lp["router_bias"].astype(f32), cfg.num_experts_per_tok
    )
    w = _chosen(scores, idx)
    if cfg.norm_topk_prob:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * cfg.routed_scaling_factor, idx


@jax.custom_vjp
def _chosen(scores: jax.Array, idx: jax.Array) -> jax.Array:
    """scores [N, E], idx [N, k] -> the chosen experts' scores [N, k].  The
    gather's own transpose is a scatter-add of N * k updates, which the
    chip runs one after the other (131,072 of them a layer in a 16k train
    step); the cotangent is placed by comparison instead."""
    return jnp.take_along_axis(scores, idx, axis=-1)


def _chosen_fwd(scores, idx):
    return _chosen(scores, idx), (idx, scores.shape[-1])


def _chosen_bwd(res, g):
    idx, E = res
    hit = idx[..., None] == jnp.arange(E, dtype=idx.dtype)  # [N, k, E]
    return jnp.sum(jnp.where(hit, g[..., None], 0.0), axis=-2), None


_chosen.defvjp(_chosen_fwd, _chosen_bwd)


def relu2(x: jax.Array) -> jax.Array:
    return jnp.square(jax.nn.relu(x))


# rows of a grouped product come in multiples of this: the TPU's grouped
# matmul kernel behind `lax.ragged_dot` takes only such row counts, and
# the compiler's fallback computes every group for every row (128 times
# the work at 128 experts; compiled for a described v5e, PR 32)
_RAGGED_ROWS = 128


def held_dispatch(cfg: TransformerConfig, lp: Params, x: jax.Array):
    """What every expert layer at a share does before its grouped products:
    the router over ALL `cfg.num_experts` (sigmoid scores, or the softmax
    of `cfg.router_kind`, whose outputs past `num_experts` are identity
    experts that no share holds), and the (token, choice) assignments
    sorted by held expert.  x [N, D] -> (weights [N, k] f32,
    held [N, k] bool: the choice is an expert of `cfg.held_range`, group
    [N, k]: its index among the held ones, `n_held` where it is held
    elsewhere, order [N * k]: the assignments by held expert, those routed
    elsewhere behind the last group)."""
    return _dispatch(cfg, lp, x)[:4]


def _dispatch(cfg: TransformerConfig, lp: Params, x: jax.Array):
    """`held_dispatch` and, behind its four, the chosen outputs idx [N, k]."""
    lo, hi = cfg.held_range
    n_held = hi - lo
    route = route_softmax_bias if cfg.router_kind == "softmax" else route_sigmoid
    w, idx = route(cfg, lp, x)  # [N, k]
    local = idx - lo
    held = (local >= 0) & (local < n_held)
    # elsewhere-routed rows sort behind the last group
    group = jnp.where(held, local, n_held)
    return w, held, group, jnp.argsort(group.reshape(-1)), idx


def group_sizes(group: jax.Array, n_held: int, compare: bool) -> jax.Array:
    """Rows of every held expert's group, int32 [n_held], from the group
    ids (`n_held` = held elsewhere).  `jnp.bincount` is a scatter-add, one
    update after the other on the chip: right for a decode pass's few
    thousand rows, not for a train step's 131,072, which are counted by
    comparison instead (`compare`)."""
    group = group.reshape(-1)
    if compare:
        return jnp.sum(
            group[:, None] == jnp.arange(n_held, dtype=group.dtype), axis=0,
            dtype=jnp.int32,
        )
    return jnp.bincount(group, length=n_held + 1)[:n_held].astype(jnp.int32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def rows_by_expert(u: jax.Array, order: jax.Array, held: jax.Array, k: int):
    """u [N, F] -> [padded, F]: the row of its token for every assignment in
    `order`, padded to the grouped product's multiple with copies of row 0
    (they lie behind the last group).  Differentiated by hand, as gathers
    only: the assignments are a permutation of (token, choice), so the
    cotangent goes back through the inverse permutation and is summed over
    a token's k choices; a scatter-add over 131,072 rows that repeat is
    serial on the chip.  Rows no held expert took carry whatever the
    product's transpose left there and are dropped."""
    rows = order.shape[0]
    padded = -(-rows // _RAGGED_ROWS) * _RAGGED_ROWS
    tok = jnp.pad(order // k, (0, padded - rows))
    return jnp.take(u, tok, axis=0)


def _rows_by_expert_fwd(u, order, held, k):
    return rows_by_expert(u, order, held, k), (order, held)


def _rows_by_expert_bwd(k, res, g):
    order, held = res
    N = held.shape[0]
    g = jnp.take(g[: order.shape[0]], jnp.argsort(order), axis=0)
    g = jnp.where(held[..., None], g.reshape(N, k, -1), jnp.zeros((), g.dtype))
    return g.sum(1), None, None


rows_by_expert.defvjp(_rows_by_expert_fwd, _rows_by_expert_bwd)


@jax.custom_vjp
def rows_by_choice(ys: jax.Array, order: jax.Array, held: jax.Array):
    """ys [padded, F] in `order` -> [N, k, F] in (token, choice) order; a
    row no held expert took (it lies behind the last group, whatever the
    kernel left there) is 0.  The transpose is the same permutation
    forward: a gather."""
    N, k = held.shape
    ys = jnp.take(ys, jnp.argsort(order), axis=0).reshape(N, k, -1)
    return jnp.where(held[..., None], ys, jnp.zeros((), ys.dtype))


def _rows_by_choice_fwd(ys, order, held):
    return rows_by_choice(ys, order, held), (order, held, ys.shape[0])


def _rows_by_choice_bwd(res, g):
    order, held, padded = res
    g = jnp.where(held[..., None], g, jnp.zeros((), g.dtype))
    g = jnp.take(g.reshape(order.shape[0], -1), order, axis=0)
    return jnp.pad(g, ((0, padded - order.shape[0]), (0, 0))), None, None


rows_by_choice.defvjp(_rows_by_choice_fwd, _rows_by_choice_bwd)


def latent_moe_ffn(
    cfg: TransformerConfig,
    lp: Params,  # one block's leaves; w1, w2 of ALL blocks and `block`
    h: jax.Array,  # [B, T, D]
    dtype,
    valid: Optional[jax.Array] = None,  # bool [B, T]: rows somebody reads
) -> Tuple[jax.Array, jax.Array]:
    """nemotron_h's expert block -> (routed + shared [B, T, D], counters
    int32 [2]: (token, expert) assignments of `valid` rows to experts held
    here, and held experts that got any row).

    Routed experts are un-gated squared-ReLU MLPs in a latent space of
    `moe_latent_size` between two latent projections; the shared expert is
    the same MLP at the model's width.  This program holds experts
    `cfg.held_range` of `cfg.num_experts`: the router chooses over all of
    them, and the rows routed to a held expert are sorted by expert and go
    through two grouped products (`lax.ragged_dot`, one group an expert;
    `held_dispatch`, shared with `gated_moe_ffn`, then `rows_by_expert`,
    `rows_by_choice`).  A row routed elsewhere lies behind the last group
    and yields zero: what the other shares would add is left out, and
    nothing stands in for the exchange with them.

    `lp["w1"]`, `lp["w2"]` are the stacked experts of every expert block
    [n_blocks, held, ...] and `lp["block"]` (static) says which block this
    is: the grouped product runs over all n_blocks * held groups with the
    other blocks' groups empty, so the stack is read where it lies.  A
    slice of it would be copied out whole for the kernel on every pass."""
    B, T, D = h.shape
    k = cfg.num_experts_per_tok
    N = B * T
    x = h.reshape(N, D)
    with jax.named_scope("moe_router"):
        w, held, group, order = held_dispatch(cfg, lp, x)
        lo, hi = cfg.held_range
        n_held = hi - lo
        sizes = group_sizes(group, n_held, compare=False)
        live = held if valid is None else held & valid.reshape(N, 1)
        counters = jnp.stack([
            jnp.sum(live, dtype=jnp.int32), jnp.sum(sizes > 0, dtype=jnp.int32)
        ])
    with jax.named_scope("moe_latent"):
        u = jnp.einsum("nd,dl->nl", x, lp["w_l1"].astype(dtype))
    with jax.named_scope("moe_experts"):
        us = rows_by_expert(u, order, held, k)  # [padded, latent]
        n_blocks, j = lp["w1"].shape[0], lp["block"]
        all_sizes = jnp.zeros((n_blocks, n_held), jnp.int32).at[j].set(sizes)
        all_sizes = all_sizes.reshape(-1)
        flat = lambda a: a.reshape((n_blocks * n_held,) + a.shape[2:])  # noqa: E731
        mid = relu2(jax.lax.ragged_dot(
            us, flat(lp["w1"]).astype(dtype), all_sizes))
        ys = jax.lax.ragged_dot(mid, flat(lp["w2"]).astype(dtype), all_sizes)
        ys = rows_by_choice(ys, order, held)  # [N, k, latent]
        lat = jnp.einsum("nkl,nk->nl", ys, w.astype(dtype))
    with jax.named_scope("moe_latent"):
        routed = jnp.einsum("nl,ld->nd", lat, lp["w_l2"].astype(dtype))
    with jax.named_scope("moe_shared"):
        mid = relu2(jnp.einsum("nd,df->nf", x, lp["ws1"].astype(dtype)))
        shared = jnp.einsum("nf,fd->nd", mid, lp["ws2"].astype(dtype))
    return (routed + shared).reshape(B, T, D), counters


def held_row_block(N: int, k: int, n_held: int, num_experts: int) -> int:
    """The static row count of `gated_moe_ffn`'s sorted buffers: one and a
    half times the rows an even router would send here, N * k * n_held /
    num_experts (a layer's held experts took 0.68 to 1.36 times their even
    share in the 16k cell: PERF.md section 5), in the grouped product's
    multiples, and never more than all N * k assignments."""
    pad = lambda r: -(-int(r) // _RAGGED_ROWS) * _RAGGED_ROWS  # noqa: E731
    return min(pad(1.5 * N * k * n_held / num_experts), pad(N * k))


def _by_rows(a, b, sizes):
    """a [R, A], b [R, B] -> [groups, A, B], a^T b over each group's rows:
    the grouped product with a ragged contraction (a grouped product's
    transpose by its weights)."""
    return jax.lax.ragged_dot_general(
        a, b, sizes,
        jax.lax.RaggedDotDimensionNumbers(
            dot_dimension_numbers=(((0,), (0,)), ((), ())),
            lhs_ragged_dimensions=[0], rhs_group_dimensions=[],
        ),
    )


def _sum_by_token(rows: jax.Array, tok: jax.Array, N: int) -> jax.Array:
    """rows [R, F], tok [R] (N = no token's) -> [N, F]: every token's rows
    summed, in float32 inside the product.  The transpose of a row gather
    `take(u, tok)`, and the weighted sum back of an expert layer at a
    share.  Built as a grouped product with a ragged CONTRACTION (the mode
    `lax.ragged_dot`'s weight gradient runs): the rows sorted by token, one
    group a block of `_RAGGED_ROWS` tokens, a one-hot [R, block] of the
    token within its block against the rows."""
    blk = _RAGGED_ROWS
    n_blocks = -(-N // blk)
    # no token's rows sort behind the last block and belong to no group
    tok = jnp.where(tok < N, tok, n_blocks * blk)
    tok, by_token = jax.lax.sort(
        (tok, jnp.arange(tok.shape[0], dtype=tok.dtype)), num_keys=1)
    rows = jnp.take(rows, by_token, axis=0)
    sizes = jnp.sum(
        (tok // blk)[:, None] == jnp.arange(n_blocks, dtype=tok.dtype), axis=0,
        dtype=jnp.int32,
    )
    place = (tok % blk)[:, None] == jnp.arange(blk, dtype=tok.dtype)
    out = _by_rows(place.astype(rows.dtype), rows, sizes)  # [n_blocks, blk, F]
    return out.reshape(n_blocks * blk, -1)[:N]


def _block_rows(off, R: int, k: int, x, w, order, sizes):
    """What forward and backward of a block both start from: rows
    [off, off + R) of the sort -> (token of each row [R], N where the row
    lies behind the last group; in_group bool [R, 1]; the rows' tokens' x
    [R, D]; the rows' own gate weights [R, 1], 0 behind the last group;
    the held experts' rows within the block, int32 [held])."""
    N = x.shape[0]
    idx = jax.lax.dynamic_slice(jnp.pad(order, (0, R)), (off,), (R,))
    ends = jnp.cumsum(sizes)
    in_group = off + jnp.arange(R) < ends[-1]
    tok = idx // k
    xs = jnp.take(x, tok, axis=0)
    tok = jnp.where(in_group, tok, N)
    wr = jnp.where(in_group, jnp.take(w.reshape(-1), idx), 0.0)
    within = jnp.minimum(ends, off + R) - jnp.maximum(ends - sizes, off)
    return (tok, in_group[:, None], xs, wr[:, None].astype(x.dtype),
            jnp.maximum(within, 0).astype(jnp.int32))


def _live(in_group, rows):
    """A row behind the last group holds whatever the grouped product left
    there: zero it."""
    return jnp.where(in_group, rows, jnp.zeros((), rows.dtype))


def _block_forward(off, R: int, k: int, x, w, order, sizes, wg, wu, wd):
    """Rows [off, off + R) of the sort through the gated experts, weighed
    and summed by token -> [N, D]."""
    tok, in_group, xs, wr, sizes = _block_rows(off, R, k, x, w, order, sizes)
    gate = jax.lax.ragged_dot(xs, wg, sizes)
    up = jax.lax.ragged_dot(xs, wu, sizes)
    mid = _live(in_group, jax.nn.silu(gate) * up)
    ys = jax.lax.ragged_dot(mid, wd, sizes)
    return _sum_by_token(_live(in_group, ys * wr), tok, x.shape[0])


def _block_backward(off, R: int, k: int, x, w, order, sizes, wg, wu, wd, g):
    """The block's transposes -> (d x [N, D], d of the rows' gate weights
    [R] float32, d wg, d wu, d wd), with `gate` and `up` computed again:
    nothing of a block's size crosses from the forward (below)."""
    N = x.shape[0]
    tok, in_group, xs, wr, sizes = _block_rows(off, R, k, x, w, order, sizes)
    swap = lambda m: jnp.swapaxes(m, 1, 2)  # noqa: E731
    gate = jax.lax.ragged_dot(xs, wg, sizes)
    up = jax.lax.ragged_dot(xs, wu, sizes)
    sig = jax.nn.sigmoid(gate)
    act = gate * sig
    mid = _live(in_group, act * up)
    gy = _live(in_group, jnp.take(g, jnp.minimum(tok, N - 1), axis=0))
    # ys = mid @ w_down is not computed again: <gy, ys> = <gy @ w_down^T, mid>
    t = _live(in_group, jax.lax.ragged_dot(gy, swap(wd), sizes))
    d_wr = jnp.sum(t.astype(jnp.float32) * mid.astype(jnp.float32), axis=-1)
    d_wd = _by_rows(mid, gy * wr, sizes)
    d_mid = t * wr
    d_up = d_mid * act
    d_gate = d_mid * up * (sig * (1 + gate * (1 - sig)))
    d_wg = _by_rows(xs, d_gate, sizes)
    d_wu = _by_rows(xs, d_up, sizes)
    d_xs = (jax.lax.ragged_dot(d_gate, swap(wg), sizes)
            + jax.lax.ragged_dot(d_up, swap(wu), sizes))
    d_x = _sum_by_token(_live(in_group, d_xs), tok, N)
    return d_x, jnp.where(in_group[:, 0], d_wr, 0.0), d_wg, d_wu, d_wd


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def routed_experts(R: int, k: int, x, w, order, sizes, wg, wu, wd):
    """The routed part of `gated_moe_ffn` -> [N, D]: x [N, D] gathered by
    held expert, gated experts `wg`, `wu` [held, D, F], `wd` [held, F, D] as
    grouped products over `sizes`, the rows weighed by their gates w [N, k]
    and summed by token, R rows of the sort at a time while rows of a held
    expert are left (`lax.while_loop`: one trip where R rows hold them all,
    none where nothing is held, and every held assignment is computed,
    whatever the routing).

    Differentiated by hand, the backward walking the same blocks: JAX does
    not transpose a loop of unknown length.  A `lax.switch` over whole
    buffers of several static sizes, which it does transpose, keeps every
    branch's temporaries beside the program's own (the 16k cell's step for
    a described v5e: 7.5 -> 10.2 GB), and a first block outside the loop
    doubles the grouped-product kernels a program has to load (PERF.md
    section 6, PR 43).  Only x, w, `order`, `sizes` and the experts cross
    from forward to backward."""
    args = (k, x, w, order, sizes, wg, wu, wd)
    held_rows = jnp.sum(sizes)

    def block(carry):
        off, out = carry
        return off + R, out + _block_forward(off, R, *args)

    _, out = jax.lax.while_loop(
        lambda carry: carry[0] < held_rows, block,
        (jnp.int32(0), jnp.zeros_like(x)),
    )
    return out


def _routed_experts_fwd(R, k, *args):
    return routed_experts(R, k, *args), args


def _routed_experts_bwd(R, k, args, g):
    x, w, order, sizes, wg, wu, wd = args
    n = order.shape[0]
    held_rows = jnp.sum(sizes)

    def block(carry):
        off, d_x, d_rows, *d_experts = carry
        b_x, b_rows, *b_experts = _block_backward(off, R, k, *args, g)
        return (off + R, d_x + b_x,
                jax.lax.dynamic_update_slice(d_rows, b_rows, (off,)),
                *(a + b for a, b in zip(d_experts, b_experts)))

    _, d_x, d_rows, d_wg, d_wu, d_wd = jax.lax.while_loop(
        lambda carry: carry[0] < held_rows, block,
        (jnp.int32(0), jnp.zeros_like(x), jnp.zeros((n + R,), jnp.float32),
         jnp.zeros_like(wg), jnp.zeros_like(wu), jnp.zeros_like(wd)),
    )
    # the rows' gate gradients back to their (token, choice): `order` is a
    # permutation, so sorting by it places them; no scatter of scalars
    _, d_w = jax.lax.sort((order, d_rows[:n]), num_keys=1)
    return d_x, d_w.reshape(w.shape).astype(w.dtype), None, None, d_wg, d_wu, d_wd


routed_experts.defvjp(_routed_experts_fwd, _routed_experts_bwd)


def _held_stack(lp: Params, sizes: jax.Array, n_held: int, dtype):
    """-> (sizes, w_gate, w_up, w_down) as `routed_experts` takes them.  A
    layer that brings its own experts [held, ...] hands them over as they
    are.  With `lp["block"]` the leaves hold the experts of ALL expert
    layers [n, held, ...]: they go in whole with this layer's index, the
    other layers' groups empty (a slice of the stack would be copied out
    for the grouped product)."""
    leaves = (lp["w_gate"], lp["w_up"], lp["w_down"])
    if "block" not in lp:
        return (sizes, *(a.astype(dtype) for a in leaves))
    n_blocks = leaves[0].shape[0]
    all_sizes = jnp.zeros((n_blocks, n_held), jnp.int32).at[lp["block"]].set(sizes)
    return (all_sizes.reshape(-1), *(
        a.reshape((n_blocks * n_held,) + a.shape[2:]).astype(dtype)
        for a in leaves))


def gated_moe_ffn(
    cfg: TransformerConfig,
    lp: Params,  # one block's leaves, the held experts' among them
    h: jax.Array,  # [B, T, D]
    dtype,
    valid: Optional[jax.Array] = None,  # bool [B, T]: rows that are tokens
    drop_invalid: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Gated (SwiGLU) experts at the model's width under the sigmoid router,
    at a share (afmoe, mimo_v2) -> (routed + shared [B, T, D], counters
    int32 [4]: (token, expert) assignments of `valid` rows to experts held
    here, the rows of the fullest held expert, the rows of the sorted
    buffers, and the held experts that got any `valid` row).

    `drop_invalid` (static; a decode pass, whose idle slots nobody reads)
    sorts the rows that are not `valid` behind the last group with those
    routed elsewhere: no expert is read for them and they come back zero.
    `lp["block"]` (a decode program's unrolled layers): `w_gate`, `w_up`
    and `w_down` are the held experts of ALL expert layers [n, held, ...]
    and `block` this layer's index, as `identity_moe_ffn` takes them.

    `latent_moe_ffn`'s router and sort (`held_dispatch`): the router scores
    all `cfg.num_experts` in float32 and chooses k by score + bias (the
    bias is a buffer: it gets no gradient), the assignments are sorted by
    held expert, those held elsewhere behind the last group.  The routed
    part is its own (`routed_experts`): of the N * k assignments the
    leading rows of the sort are gathered, `w_gate`, `w_up` and `w_down`
    [held, ...] run as `lax.ragged_dot` over the held experts' groups, and
    the rows are weighed and summed by token, all on buffers of a static
    size (`held_row_block`) of which the device runs as many as the rows
    the held experts took need; what the experts elsewhere would add is
    left out.
    Trains: the gradient reaches the router through the weights `w`, the
    experts through the grouped products' two transposes, and the tokens
    through the transpose of the gather, `_sum_by_token`."""
    B, T, D = h.shape
    k = cfg.num_experts_per_tok
    N = B * T
    x = h.reshape(N, D)
    act = jax.nn.silu
    lo, hi = cfg.held_range
    n_held = hi - lo
    R = held_row_block(N, k, n_held, cfg.num_experts)
    with jax.named_scope("moe_router"):
        rp = {**lp, "router_bias": jax.lax.stop_gradient(lp["router_bias"])}
        w, held, group, order = held_dispatch(cfg, rp, x)
        if drop_invalid and valid is not None:
            group = jnp.where(valid.reshape(N, 1), group, n_held)
            order = jnp.argsort(group.reshape(-1))
        sizes = group_sizes(group, n_held, compare=True)
        live = held if valid is None else held & valid.reshape(N, 1)
        load = group_sizes(jnp.where(live, group, n_held), n_held, compare=True)
        # the loop below takes a block of R rows while held rows are left
        buffered = R * ((jnp.sum(sizes) + R - 1) // R)
        counters = jnp.stack([
            jnp.sum(load), jnp.max(load), buffered,
            jnp.sum(load > 0, dtype=jnp.int32),
        ])
    with jax.named_scope("moe_experts"):
        routed = routed_experts(
            R, k, x, w, order, *_held_stack(lp, sizes, n_held, dtype))
    if "ws_gate" not in lp:  # a block without a shared expert
        return routed.reshape(B, T, D), counters
    with jax.named_scope("moe_shared"):
        mid = act(jnp.einsum("nd,df->nf", x, lp["ws_gate"].astype(dtype)))
        mid = mid * jnp.einsum("nd,df->nf", x, lp["ws_up"].astype(dtype))
        shared = jnp.einsum("nf,fd->nd", mid, lp["ws_down"].astype(dtype))
    return (routed + shared).reshape(B, T, D), counters


# what `identity_moe_ffn` counts, in the order of its counters
IDENTITY_MOE_COUNTERS = (
    "expert_assignments", "identity_assignments", "expert_assignments_held",
    "experts_touched",
)


def identity_moe_ffn(
    cfg: TransformerConfig,
    lp: Params,  # one layer's router leaves; w_gate, w_up, w_down of ALL
    #              layers [L, held, ...] and `block`, this layer's index
    h: jax.Array,  # [B, T, D]
    dtype,
    valid: Optional[jax.Array] = None,  # bool [B, T]: rows somebody reads
) -> Tuple[jax.Array, jax.Array]:
    """longcat_flash's expert layer at a share -> (routed + identity
    [B, T, D], counters int32 [4] over `valid` rows, by
    `IDENTITY_MOE_COUNTERS`: assignments in all (k a token), those to an
    identity expert, those to an expert held here, and held experts that
    got any row).

    The softmax router scores `num_experts` routed experts and, behind
    them, `zero_expert_num` identity experts, and chooses k of them all
    (`route_softmax_bias`).  An assignment to an identity expert adds its
    weight times the token itself: the weights of a token's identity
    choices are summed and the token is scaled once, so those assignments
    never enter the sorted buffers.  Every share computes that part alike
    (it needs no weights); it is counted once when shares are summed.  The
    routed part is `gated_moe_ffn`'s (`held_dispatch`, `routed_experts`):
    the experts held here as grouped products over the rows routed to them;
    what the experts elsewhere would add is left out.  No shared expert.

    The held experts of every layer go in whole with this layer's index,
    the other layers' groups empty, as `latent_moe_ffn` takes them: a slice
    of the stack would be copied out for the grouped product."""
    B, T, D = h.shape
    k = cfg.num_experts_per_tok
    N = B * T
    x = h.reshape(N, D)
    lo, hi = cfg.held_range
    n_held = hi - lo
    R = held_row_block(N, k, n_held, cfg.num_experts + cfg.zero_expert_num)
    with jax.named_scope("moe_router"):
        w, held, group, order, idx = _dispatch(cfg, lp, x)
        sizes = group_sizes(group, n_held, compare=True)
        identity = idx >= cfg.num_experts  # [N, k]
        live = jnp.ones((N, 1), bool) if valid is None else valid.reshape(N, 1)
        counters = jnp.stack([
            jnp.sum(live, dtype=jnp.int32) * k,
            jnp.sum(identity & live, dtype=jnp.int32),
            jnp.sum(held & live, dtype=jnp.int32),
            jnp.sum(sizes > 0, dtype=jnp.int32),
        ])
    with jax.named_scope("moe_experts"):
        routed = routed_experts(
            R, k, x, w, order, *_held_stack(lp, sizes, n_held, dtype))
    with jax.named_scope("moe_identity"):
        w_id = jnp.sum(jnp.where(identity, w, 0.0), axis=-1, keepdims=True)
        out = routed + (w_id * x.astype(jnp.float32)).astype(dtype)
    return out.reshape(B, T, D), counters


def _aux_loss(probs: jax.Array, gate_idx: jax.Array, E: int) -> jax.Array:
    """Switch load-balancing loss: E * sum_i f_i * P_i where f_i is the
    fraction of tokens whose FIRST choice is expert i and P_i the mean
    router probability for i."""
    first = jax.nn.one_hot(gate_idx[:, 0], E, dtype=jnp.float32)
    f = jnp.mean(first, axis=0)
    p = jnp.mean(probs, axis=0)
    return jnp.asarray(E, jnp.float32) * jnp.sum(f * p)


def moe_ffn(
    cfg: TransformerConfig,
    lp: Params,  # router [D, E], w_gate/w_up [E, D, Fm], w_down [E, Fm, D]
    h: jax.Array,  # [B, T, D]
    dtype,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (output [B, T, D], load-balance aux loss scalar fp32).

    cfg.moe_impl picks the dispatch: "capacity" (GShard dense dispatch,
    tokens past the per-expert budget dropped) or "dropless" (exact HF
    Mixtral/Qwen3-MoE semantics via sort + grouped GEMM)."""
    if cfg.moe_impl == "dropless":
        return _moe_ffn_dropless(cfg, lp, h, dtype)
    return _moe_ffn_capacity(cfg, lp, h, dtype)


def _moe_ffn_dropless(
    cfg: TransformerConfig, lp: Params, h: jax.Array, dtype
) -> Tuple[jax.Array, jax.Array]:
    """Dropless token routing — the semantics real HF MoE checkpoints were
    trained with (HF MixtralSparseMoeBlock / Qwen3MoeSparseMoeBlock apply
    every top-k assignment with no capacity bound), so loaded checkpoints
    produce batch-size-independent logits (ADVICE r3).

    TPU shape: sort the N*k (token, expert) assignments by expert id, run
    one grouped GEMM per projection with `lax.ragged_dot` (MegaBlocks-style
    — the expert boundary is a group-sizes vector, shapes stay static), and
    scatter-add weighted outputs back.  FLOPs equal capacity-mode at factor
    1.0 with zero drops; no [N, E, C] dispatch tensors are materialised."""
    B, T, D = h.shape
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    N = B * T
    x = h.reshape(N, D)
    probs, gate_vals, gate_idx = _route(lp, x, k, cfg.norm_topk_prob)

    e_flat = gate_idx.reshape(-1)  # [N*k] expert id per assignment
    order = jnp.argsort(e_flat)  # stable: preserves token order per expert
    tok = order // k  # source token per sorted assignment
    xs = jnp.take(x, tok, axis=0)  # [N*k, D]
    group_sizes = jnp.bincount(e_flat, length=E).astype(jnp.int32)

    gate = jax.lax.ragged_dot(xs, lp["w_gate"].astype(dtype), group_sizes)
    up = jax.lax.ragged_dot(xs, lp["w_up"].astype(dtype), group_sizes)
    ys = jax.lax.ragged_dot(
        jax.nn.silu(gate) * up, lp["w_down"].astype(dtype), group_sizes
    )  # [N*k, D]

    w_sorted = jnp.take(gate_vals.reshape(-1), order).astype(dtype)
    out = jnp.zeros((N, D), dtype).at[tok].add(ys * w_sorted[:, None])
    return out.reshape(B, T, D), _aux_loss(probs, gate_idx, E)


def _moe_ffn_capacity(
    cfg: TransformerConfig, lp: Params, h: jax.Array, dtype
) -> Tuple[jax.Array, jax.Array]:
    B, T, D = h.shape
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    N = B * T
    C = expert_capacity(N, E, k, cfg.moe_capacity_factor)
    x = h.reshape(N, D)
    probs, gate_vals, gate_idx = _route(lp, x, k, cfg.norm_topk_prob)

    # position-in-expert assignment, choice-major priority (first choices
    # beat second choices for capacity, standard GShard ordering)
    dispatch = jnp.zeros((N, E, C), jnp.float32)
    combine = jnp.zeros((N, E, C), jnp.float32)
    fill = jnp.zeros((E,), jnp.float32)
    for j in range(k):  # k is tiny and static
        oh = jax.nn.one_hot(gate_idx[:, j], E, dtype=jnp.float32)  # [N, E]
        pos = jnp.cumsum(oh, axis=0) - 1 + fill[None, :]  # [N, E]
        keep = oh * (pos < C)
        slot = jax.nn.one_hot(
            jnp.sum(pos * oh, axis=-1).astype(jnp.int32), C, dtype=jnp.float32
        )  # [N, C]
        d_j = keep[:, :, None] * slot[:, None, :]  # [N, E, C]
        dispatch = dispatch + d_j
        combine = combine + d_j * gate_vals[:, j, None, None]
        fill = fill + jnp.sum(oh, axis=0)

    xe = jnp.einsum("nec,nd->ecd", dispatch.astype(dtype), x)  # [E, C, D]
    gate = jnp.einsum("ecd,edf->ecf", xe, lp["w_gate"].astype(dtype))
    up = jnp.einsum("ecd,edf->ecf", xe, lp["w_up"].astype(dtype))
    ye = jnp.einsum(
        "ecf,efd->ecd", jax.nn.silu(gate) * up, lp["w_down"].astype(dtype)
    )  # [E, C, D]
    out = jnp.einsum("nec,ecd->nd", combine.astype(dtype), ye)
    return out.reshape(B, T, D), _aux_loss(probs, gate_idx, E)
