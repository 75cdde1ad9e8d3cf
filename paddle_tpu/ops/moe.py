"""Mixture-of-Experts routing and expert-parallel FFN.

Beyond-reference capability (expert parallelism in the SURVEY §2
parallelism table). Switch-style fixed-capacity TOP-1 routing:
token->expert assignment becomes dense dispatch/combine einsum tensors
(static shapes, MXU-friendly), so XLA's GSPMD inserts the all-to-all
when the expert axis of the expert weights is sharded over the mesh.
Tokens route within fixed-size GROUPS (GShard's [G, S, ...] layout) so
dispatch tensors stay O(N * group_size) instead of O(N^2). Aux
load-balancing loss per GShard/Switch eq. 4.

Beside it, the present-day path (`dropless_moe`): top-k routing (softmax
over the experts, or a sigmoid a logit with a selection bias and a scale)
over ALL the experts, for a layer that holds a contiguous share of
them (`held_first`, as many as its weights have), with no capacity and no
dropped token. The (token, expert) slots are sorted by expert, held experts
first; one grouped matrix product a projection runs over the held experts'
row groups (`grouped_matmul`: the TPU's megablox kernel, `lax.ragged_dot`
elsewhere); each token then sums its held slots' rows, weighted. No tensor
grows with experts x capacity: the row buffer is tokens x top-k, the worst
case, and NOTHING works through more of it than the slots held: the kernel
goes by the real group sizes, and every other pass (the dispatch's gather,
the gated activation, the combine, and the backward of each) runs chunk by
chunk of `CHUNK` rows under a trip count taken from the held count, which
is data: shapes are fixed and nothing retraces. Rows past the held ones are
never written and never read.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from paddle_tpu import ops as _ops


def top1_routing(
    gate_logits: jax.Array,
    capacity: int,
    token_mask: jax.Array = None,
):
    """gate_logits [N, E] -> (dispatch [N, E, C] one-hot, combine
    [N, E, C] prob-weighted, aux_loss scalar).

    Tokens beyond an expert's capacity C are dropped (standard Switch
    behavior); position within the expert buffer is the token's rank
    among tokens routed to that expert. `token_mask` [N] (1 = real)
    excludes padded tokens BEFORE the rank cumsum so padding never
    consumes expert capacity or skews the balance statistics.
    """
    N, E = gate_logits.shape
    probs = jax.nn.softmax(gate_logits, axis=-1)
    expert = jnp.argmax(probs, axis=-1)  # [N]
    gate = jnp.max(probs, axis=-1)  # [N]

    # rank accounting runs in float32 REGARDLESS of the activation
    # dtype: a bfloat16 cumsum loses integer exactness past 256 and
    # silently collides capacity slots under AMP
    onehot = jax.nn.one_hot(expert, E, dtype=jnp.float32)  # [N, E]
    if token_mask is not None:
        onehot = onehot * token_mask.astype(jnp.float32)[:, None]
    # rank of each token within its expert (0-based arrival order)
    pos = jnp.cumsum(onehot, axis=0) - onehot  # [N, E]
    pos_in_expert = jnp.sum(pos * onehot, axis=-1).astype(jnp.int32)  # [N]
    keep = pos_in_expert < capacity
    if token_mask is not None:
        keep = keep & (token_mask > 0)
    gate = gate * keep

    dispatch = (
        onehot[:, :, None]
        * jax.nn.one_hot(pos_in_expert, capacity, dtype=jnp.float32)[
            :, None, :
        ]
        * keep[:, None, None]
    ).astype(probs.dtype)  # [N, E, C]
    combine = dispatch * gate[:, None, None]

    # load-balancing aux loss (Switch eq. 4): E * sum_e f_e * p_e,
    # statistics over REAL tokens only
    if token_mask is None:
        denom = float(N)
        probs_sum = jnp.sum(probs, axis=0)
    else:
        denom = jnp.maximum(jnp.sum(token_mask), 1.0)
        probs_sum = jnp.sum(probs * token_mask[:, None], axis=0)
    frac_tokens = jnp.sum(onehot, axis=0) / denom  # f_e
    frac_probs = probs_sum / denom  # p_e
    aux = E * jnp.sum(frac_tokens * frac_probs)
    return dispatch, combine, aux




def moe_ffn(
    x: jax.Array,
    router_w: jax.Array,
    w_in: jax.Array,
    w_out: jax.Array,
    capacity_factor: float = 1.25,
    activation=jax.nn.relu,
    token_mask: jax.Array = None,
    group_size: int = 1024,
):
    """x [N, D]; router_w [D, E]; w_in [E, D, H]; w_out [E, H, D].
    Returns (y [N, D], aux_loss). token_mask [N] excludes padding from
    routing entirely.

    Tokens route within groups of S = min(group_size, N); N is padded up
    to a multiple of S with masked tokens, so dispatch/combine are
    [G, S, E, C] with
    C = cf*S/E: memory and FLOPs stay O(N * group_size), GShard's
    grouped layout, instead of O(N^2) for one global group.

    Shard w_in/w_out on the expert axis (PartitionSpec("model" | "expert"
    , ...)) for expert parallelism — the dispatch einsum then lowers to
    an all-to-all over ICI.
    """
    N = x.shape[0]
    E = router_w.shape[1]
    S = min(group_size, N)
    # pad to a multiple of S with MASKED tokens so grouping never
    # degenerates (a prime N must not collapse to one-token groups,
    # which would disable capacity discipline entirely)
    G = -(-N // S)
    pad = G * S - N
    mask = (
        token_mask.astype(jnp.float32)
        if token_mask is not None
        else jnp.ones((N,), jnp.float32)
    )
    if pad:
        x = jnp.concatenate(
            [x, jnp.zeros((pad,) + x.shape[1:], x.dtype)]
        )
        mask = jnp.concatenate([mask, jnp.zeros((pad,), mask.dtype)])
    capacity = max(int(capacity_factor * S / E), 1)
    logits = (x @ router_w).reshape(G, S, E)
    xg = x.reshape(G, S, -1)
    mg = mask.reshape(G, S)
    dispatch, combine, aux = jax.vmap(
        lambda l, m: top1_routing(l, capacity, token_mask=m)
    )(logits, mg)
    # [G, E, C, D]: per-group expert input buffers
    xin = jnp.einsum("gsd,gsec->gecd", xg, dispatch)
    h = activation(jnp.einsum("gecd,edh->gech", xin, w_in))
    yout = jnp.einsum("gech,ehd->gecd", h, w_out)
    y = jnp.einsum("gecd,gsec->gsd", yout, combine)
    # aux weighted by each group's REAL token count: all-padding groups
    # contribute nothing, preserving the ungrouped loss semantics
    real_g = jnp.sum(mg, axis=1)
    aux = jnp.sum(aux * real_g) / jnp.maximum(jnp.sum(real_g), 1.0)
    return y.reshape(G * S, -1)[:N], aux


# ---- dropless top-k routing over a held share of the experts ----

def route_topk(x, router_w, top_k: int, norm_topk: bool = True, *,
               scoring: str = "softmax", bias=None, scale: float = 1.0):
    """x [N, D], router_w [D, E] -> (weights [N, k] float32, experts
    [N, k] int32). Logits, scores and top-k are float32 whatever the
    operands' dtypes: the product is taken of their float32 values at the
    highest precision (a TPU's default rounds a float32 product's operands
    to bfloat16), since a logit's last bits decide which experts a token
    gets. `scoring`: "softmax" over the experts, or "sigmoid" of each
    logit alone. `bias` [E] is added to the scores for the CHOICE only (a
    selection bias, which no gradient reaches): an expert's weight is its
    score without it. `scale` multiplies the weights after `norm_topk`
    has made them sum to 1."""
    logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if scoring == "softmax":
        score, eps = jax.nn.softmax(logits, axis=-1), None
    elif scoring == "sigmoid":
        # the family's own guard: k sigmoids can all be 0 in float32
        score, eps = jax.nn.sigmoid(logits), 1e-20
    else:
        raise ValueError(f"unknown router scoring {scoring!r}")
    if bias is None:
        top, idx = jax.lax.top_k(score, top_k)
    else:
        _, idx = jax.lax.top_k(score + bias.astype(jnp.float32), top_k)
        top = jnp.take_along_axis(score, idx, axis=-1)
    if norm_topk:
        total = jnp.sum(top, axis=-1, keepdims=True)
        top = top / (total if eps is None else total + eps)
    if scale != 1.0:
        top = top * scale
    return top, idx.astype(jnp.int32)


# The grouped products' tiles: rows a tile, and the largest tile of the
# contracted and the output dimension. A smaller tile than MIN_TILE streams
# the operand the grid does not tile on that dimension once a tile again.
LANES, ROW_TILE, TILE_CAP, MIN_TILE = 128, 512, 1024, 512


def _tile(n: int, cap: int) -> int:
    """The tile of a contracted or output dimension of n: n itself where it
    is at most cap, else the lane multiple in [MIN_TILE, cap] that pads n
    least, the larger of two that pad it alike. A divisor pads nothing, so
    the largest divisor there wins; a width with none there (1,408 = 11 x
    128) takes a tile that covers it in a few pieces, and the kernel masks
    the remainder."""
    if n <= cap:
        return n
    top = cap // LANES * LANES
    return min(range(top, min(MIN_TILE, top) - 1, -LANES),
               key=lambda t: -n % t)


def _row_tile(m: int) -> int:
    """Rows a tile: the kernel's groups are laid out in whole row tiles, so
    the largest lane multiple <= ROW_TILE that divides m."""
    if m <= ROW_TILE:
        return m
    return next((t for t in range(ROW_TILE, LANES - 1, -LANES)
                 if m % t == 0), ROW_TILE)


def _tiling(m: int, k: int, n: int):
    """(rows, contracted, output) tiles of a call, and `moe.gmm_calls` by
    `path` ("divides": every tile divides its dimension; "masked": the
    kernel masks a remainder), raised once a TRACED call, read by nobody on
    the step's path."""
    from paddle_tpu import obs

    tiles = (_row_tile(m), _tile(k, TILE_CAP), _tile(n, TILE_CAP))
    path = ("divides" if k % tiles[1] == 0 and n % tiles[2] == 0
            else "masked")
    obs.get_registry().counter("moe.gmm_calls").inc(1, path=path)
    return tiles


def _megablox():
    """The kernels' module (the package's own `gmm` name is its wrapped
    function, which hides the module of the same name)."""
    import importlib

    return importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")


def _gmm_kernel(lhs, rhs, group_sizes, transpose_rhs, interpret):
    backend = _megablox()

    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    return backend.gmm(lhs, rhs, group_sizes, lhs.dtype, _tiling(m, k, n),
                       transpose_rhs=transpose_rhs, interpret=interpret)


def _tgmm_kernel(lhs, grad, group_sizes, interpret):
    """[G, k, n]: lhs rows^T @ grad rows, group by group."""
    backend = _megablox()

    m, k = lhs.shape
    return backend.tgmm(lhs.swapaxes(0, 1), grad, group_sizes, lhs.dtype,
                        _tiling(m, k, grad.shape[1]), interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gmm_pallas(lhs, rhs, group_sizes, interpret):
    with jax.named_scope("moe.gmm"):
        return _gmm_kernel(lhs, rhs, group_sizes, False, interpret)


def _gmm_pallas_fwd(lhs, rhs, group_sizes, interpret):
    return (_gmm_pallas(lhs, rhs, group_sizes, interpret),
            (lhs, rhs, group_sizes))


def _gmm_pallas_bwd(interpret, res, g):
    lhs, rhs, group_sizes = res
    g = g.astype(lhs.dtype)
    with jax.named_scope("moe.gmm"):
        dlhs = _gmm_kernel(g, rhs, group_sizes, True, interpret)
        drhs = _tgmm_kernel(lhs, g, group_sizes, interpret)
    return dlhs, drhs.astype(rhs.dtype), None


_gmm_pallas.defvjp(_gmm_pallas_fwd, _gmm_pallas_bwd)


def grouped_matmul(lhs, rhs, group_sizes, impl=None, interpret=None):
    """out[r] = lhs[r] @ rhs[g] for the rows r of group g, groups laid end
    to end from row 0 in the order of `group_sizes` [G] (int32). lhs
    [m, k], rhs [G, k, n]. ROWS PAST THE LAST GROUP ARE NOT DEFINED, in
    the result and in the gradient of `lhs` alike (the kernel never
    visits them and leaves them as they lay in memory; no pass is spent
    on zeroing them): the caller keeps them out of what it sums
    (`dropless_moe` does, once on the way out and once on the way back).
    `impl`: "pallas" (megablox, with its transposed kernels for the
    backward pass), "ragged" (`lax.ragged_dot`), None = pallas on a TPU."""
    if impl is None:
        impl = "pallas" if jax.default_backend() == "tpu" else "ragged"
    if impl == "pallas":
        return _gmm_pallas(lhs, rhs, group_sizes,
                           _ops.pallas_interpret(interpret))
    if impl == "ragged":
        with jax.named_scope("moe.gmm"):
            return jax.lax.ragged_dot(lhs, rhs, group_sizes)
    raise ValueError(f"unknown grouped matmul impl {impl!r}")


# Rows a pass over the row buffer, or over the tokens, takes at a time
# (fitted on the chip on both decoder cells: PERF.md section 6, PR 34). A
# pass costs at most a chunk more than the held rows on the expert-major
# side, and top_k chunks more on the token-major side.
CHUNK = 512


def _chunk_of(total: int) -> int:
    """The rows a trip takes of `total`: the largest divisor of it that is
    not over CHUNK, so that a buffer is whole chunks and a trip reads and
    writes one by its index (an update at a row offset the compiler cannot
    see to be aligned is a slower copy)."""
    return next(c for c in range(min(CHUNK, total), 0, -1) if total % c == 0)


def _trips(upto, total: int):
    chunk = _chunk_of(total)
    return (upto + chunk - 1) // chunk


def _chunked(a, axis=0):
    """a [.., total, ..] -> [.., total / chunk, chunk, ..] along `axis`."""
    chunk = _chunk_of(a.shape[axis])
    return a.reshape(a.shape[:axis] + (a.shape[axis] // chunk, chunk)
                     + a.shape[axis + 1:])


def _at(a, i, axis=0):
    """Chunk `i` of a chunked array."""
    return jax.lax.dynamic_index_in_dim(a, i, axis, keepdims=False)


def _put(a, chunk, i):
    return jax.lax.dynamic_update_index_in_dim(a, chunk, i, 0)


def _chunks(upto, total: int, body, carry):
    """carry = body(i, carry) for the chunks i that cover rows 0 .. `upto`
    of `total`; `upto` is a device scalar: the trip count is data, and no
    shape. The loop has no reverse-mode rule: every caller is one side of a
    `custom_vjp`."""
    return jax.lax.fori_loop(0, _trips(upto, total), body, carry)


class _ByToken(NamedTuple):
    """The held slots from the tokens' side, tokens ordered by how many of
    their slots are held, most first (position p), a token's held slots in
    slot order (column c): the tokens with more than c held slots are the
    first of that order, so a pass over column c is a pass over a prefix."""
    row: jax.Array     # [k, N] sorted row of p's c-th held slot
    col: jax.Array     # [k, N] which of the token's k choices that slot is
    held: jax.Array    # [N] how many p holds, descending
    tokens: jax.Array  # [N] p -> token
    rank: jax.Array    # [N] token -> p


def _by_token(inverse, here, n: int) -> _ByToken:
    """Sorts and one-hots over a token's k slots, no gather or scatter of
    N * k scalars: one such costs the chip what nine sorts of them do."""
    k = inverse.shape[0] // n
    rows = inverse.reshape(n, k)
    on = rows < here
    held = jnp.sum(on, axis=1, dtype=jnp.int32)
    nth = jnp.cumsum(on, axis=1, dtype=jnp.int32) - 1
    choice = jnp.arange(k, dtype=jnp.int32)
    pick = on[:, :, None] & (nth[:, :, None] == choice)     # [N, slot, c]
    col = jnp.sum(jnp.where(pick, choice[None, :, None], 0), axis=1)
    row = jnp.sum(jnp.where(pick, rows[:, :, None], 0), axis=1)
    tokens = jnp.argsort(-held, stable=True).astype(jnp.int32)
    return _ByToken(row[tokens].T, col[tokens].T, held[tokens], tokens,
                    jnp.argsort(tokens).astype(jnp.int32))


def _sum_by_token(sources, weight, plan: _ByToken):
    """y[t] = the sum over token t's held slots, in slot order and float32,
    of weight[slot] (1 without `weight`) times the sources' rows of that
    slot; `sources`: row buffers [N * k, D] in sorted order. Column by
    column over the prefix of tokens that have such a slot: the rows
    gathered are the held slots', plus at most a chunk a column."""
    n, (k, _) = plan.held.shape[0], plan.row.shape
    d, dtype = sources[0].shape[1], sources[0].dtype
    w = None
    if weight is not None:                   # [k, N]: weight[token, col]
        w = jnp.sum(jnp.where(
            plan.col[:, :, None] == jnp.arange(k, dtype=jnp.int32),
            weight[plan.tokens][None], 0), axis=2)

    held_of, row_of = _chunked(plan.held), _chunked(plan.row, 1)
    scale_of = None if w is None else _chunked(w, 1)

    def tokens(i, y):
        held, row = _at(held_of, i), _at(row_of, i, 1)
        scale = None if w is None else _at(scale_of, i, 1)

        def column(c, acc):
            part = sum(s[row[c]].astype(jnp.float32) for s in sources)
            if scale is not None:
                part = part * scale[c][:, None]
            # a row of a slot not held is not defined: chosen, not scaled
            return acc + jnp.where((c < held)[:, None], part, 0)

        acc = jax.lax.fori_loop(0, held[0], column,
                                jnp.zeros((held.shape[0], d), jnp.float32))
        return _put(y, acc.astype(dtype), i)

    y = _chunks(jnp.sum(plan.held > 0), n, tokens,
                _chunked(jnp.zeros((n, d), dtype)))
    return y.reshape(n, d)[plan.rank]


@jax.custom_vjp
def _take_tokens(x, order, here, plan):
    """x [N, D] -> the row of each sorted slot's token [N * k, D], for the
    first `here` sorted slots (those on experts held) and the rest of their
    last chunk; rows past that are not defined. Twice over, for the two
    products that read it: their cotangents then come back apart, and
    nothing adds them over the whole buffer."""
    k = order.shape[0] // x.shape[0]
    token_of = _chunked(order // k)

    def body(i, rows):
        return _put(rows, x[_at(token_of, i)], i)

    rows = _chunks(here, order.shape[0], body, _chunked(
        jax.lax.empty((order.shape[0], x.shape[1]), x.dtype)))
    rows = rows.reshape(order.shape[0], x.shape[1])
    return rows, rows


def _take_tokens_fwd(x, order, here, plan):
    return _take_tokens(x, order, here, plan), plan


def _take_tokens_bwd(plan, gs):
    # a gather and a sum, not a scatter-add; the rows of slots no held
    # expert owns were never defined (grouped_matmul) and are left out
    return _sum_by_token(gs, None, plan), None, None, None


_take_tokens.defvjp(_take_tokens_fwd, _take_tokens_bwd)


@jax.custom_vjp
def _combine(out, weight, order, inverse, here, plan):
    """out [N * k, D] in sorted order, weight [N, k] -> y [N, D]: a token's
    held slots' rows, weighted and summed. No [N, k, D] array is made on
    the way out or back, and the residuals are arguments: a recomputed
    forward of this op feeds nothing."""
    return _sum_by_token((out,), weight, plan)


def _combine_fwd(out, weight, order, inverse, here, plan):
    return (_combine(out, weight, order, inverse, here, plan),
            (out, weight, order, inverse, here))


def _combine_bwd(res, g):
    out, weight, order, inverse, here = res
    n, k = weight.shape
    # the weights in sorted order, and below the cotangents back in slot
    # order: a permutation is applied by a sort on its inverse
    _, sorted_w = jax.lax.sort((inverse, weight.reshape(-1)), num_keys=1)
    token_of, weight_of = _chunked(order // k), _chunked(sorted_w)

    def body(i, carry):
        d_out, d_w = carry
        got = g[_at(token_of, i)].astype(jnp.float32)
        mine = jnp.sum(got * _at(d_out, i).astype(jnp.float32), axis=1)
        # the chunk is read before it is written: said, or the compiler
        # may order the write first and copy the whole buffer to read from
        mine, d_out = jax.lax.optimization_barrier((mine, d_out))
        d_out = _put(d_out, (_at(weight_of, i)[:, None] * got).astype(
            out.dtype), i)
        return d_out, _put(d_w, mine, i)

    # a chunk of `out` is read, then its cotangent takes its place: nothing
    # else reads `out` after this, and the step holds a row buffer fewer
    d_out, d_w = _chunks(here, n * k, body, (
        _chunked(out), _chunked(jax.lax.empty((n * k,), jnp.float32))))
    d_out, d_w = d_out.reshape(out.shape), d_w.reshape(n * k)
    # rows past `here` hold what lay there: chosen away, slot by slot
    _, d_w = jax.lax.sort((order, d_w), num_keys=1)
    d_weight = jnp.where(inverse < here, d_w, 0).reshape(n, k)
    return d_out, d_weight.astype(weight.dtype), None, None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def _gate(activation, gate, up):
    return activation(gate) * up


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _gated(activation, gate, up, here):
    """activation(gate) * up over the first `here` rows of [N * k, H]."""
    gate_of, up_of = _chunked(gate), _chunked(up)

    def body(i, hidden):
        return _put(hidden, _gate(activation, _at(gate_of, i), _at(up_of, i)),
                    i)

    return _chunks(here, gate.shape[0], body, _chunked(
        jax.lax.empty(gate.shape, gate.dtype))).reshape(gate.shape)


def _gated_fwd(activation, gate, up, here):
    return _gated(activation, gate, up, here), (gate, up, here)


def _gated_bwd(activation, res, g):
    gate, up, here = res
    g_of = _chunked(g)

    def body(i, carry):
        _, pull = jax.vjp(functools.partial(_gate, activation),
                          *(_at(a, i) for a in carry))
        return tuple(_put(a, d, i) for a, d in zip(carry, pull(_at(g_of, i))))

    # each cotangent takes its operand's place, chunk by chunk
    d_gate, d_up = _chunks(here, gate.shape[0], body,
                           (_chunked(gate), _chunked(up)))
    return d_gate.reshape(gate.shape), d_up.reshape(up.shape), None


_gated.defvjp(_gated_fwd, _gated_bwd)


def dropless_moe(x, router_w, w_gate, w_up, w_down, *, top_k: int,
                 held_first: int = 0, norm_topk: bool = True,
                 scoring: str = "softmax", select_bias=None,
                 routed_scale: float = 1.0,
                 activation=jax.nn.silu, token_mask=None, impl=None):
    """The held experts' part of a top-k mixture's result, no token dropped.

    x [N, D]; router_w [D, E] over ALL E experts; w_gate, w_up [Eh, D, H]
    and w_down [Eh, H, D] of the Eh experts held here, experts
    `held_first` .. `held_first + Eh`; an expert is `(activation(x w_gate)
    * (x w_up)) w_down`. `token_mask` [N] (1 = real) keeps padding out of
    every expert. -> (y [N, D], stats [4] float32: slots routed, slots on
    experts held here, the fullest held expert's load over the mean, the
    rows the dispatch moved). The row buffer is all N * k slots, so there
    is nothing to drop and no count of it; every pass over it (dispatch,
    the gated activation, combine, and their backward passes) stops a chunk
    past the `here` rows of slots held, as the kernels do: when every slot
    is held every chunk runs."""
    n, d = x.shape
    e, eh = router_w.shape[1], w_up.shape[0]
    with jax.named_scope("moe.route"):
        weight, expert = route_topk(x, router_w, top_k, norm_topk,
                                    scoring=scoring, bias=select_bias,
                                    scale=routed_scale)
        # held experts first: key 0 .. Eh-1; absent ones after; padding last
        key = jnp.mod(expert - held_first, e).reshape(-1)
        if token_mask is not None:
            real = jnp.repeat(token_mask > 0, top_k)
            key = jnp.where(real, key, e)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        # by a sort and a one-hot: a scatter of N * k scalars (into the
        # inverse, or into the counts) costs the chip nine such sorts
        inverse = jnp.argsort(order).astype(jnp.int32)
        counts = jnp.sum(key[:, None] == jnp.arange(e + 1, dtype=key.dtype),
                         axis=0, dtype=jnp.int32)
        group_sizes = counts[:eh]
        here = jnp.sum(group_sizes)
        plan = _by_token(inverse, here, n)
    with jax.named_scope("moe.dispatch"):
        rows, rows_again = _take_tokens(x, order, here, plan)  # [N * k, D]
    with jax.named_scope("moe.experts"):
        up = grouped_matmul(rows, w_up, group_sizes, impl)
        hidden = _gated(activation, grouped_matmul(
            rows_again, w_gate, group_sizes, impl), up, here)
        out = grouped_matmul(hidden.astype(x.dtype), w_down, group_sizes,
                             impl)
    with jax.named_scope("moe.combine"):
        y = _combine(out, weight, order, inverse, here, plan)
    real_slots = jnp.sum(counts[:e])
    stats = jnp.stack([
        real_slots, here,
        jnp.max(group_sizes) * eh / jnp.maximum(here, 1),
        _trips(here, n * top_k) * _chunk_of(n * top_k),
    ]).astype(jnp.float32)
    return y.astype(x.dtype), stats
