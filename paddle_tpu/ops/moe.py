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
elsewhere); a weighted gather brings the rows back. No tensor grows with
experts x capacity: the row buffer is tokens x top-k, the worst case, and
the kernel works only through the real group sizes.
"""

from __future__ import annotations

import functools

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


def _tile(n: int, cap: int) -> int:
    """A tile of a lane multiple for a dimension of n: the largest <= cap
    that divides n, else cap (the kernel masks the remainder)."""
    if n <= cap:
        return n
    for t in range(cap // 128 * 128, 127, -128):
        if n % t == 0:
            return t
    return cap // 128 * 128


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
    tiling = (_tile(m, 512), _tile(k, 1024), _tile(n, 1024))
    return backend.gmm(lhs, rhs, group_sizes, lhs.dtype, tiling,
                       transpose_rhs=transpose_rhs, interpret=interpret)


def _tgmm_kernel(lhs, grad, group_sizes, interpret):
    """[G, k, n]: lhs rows^T @ grad rows, group by group."""
    backend = _megablox()

    m, k = lhs.shape
    n = grad.shape[1]
    tiling = (_tile(m, 512), _tile(k, 1024), _tile(n, 1024))
    return backend.tgmm(lhs.swapaxes(0, 1), grad, group_sizes, lhs.dtype,
                        tiling, interpret=interpret)


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


@jax.custom_vjp
def _take_tokens(x, order, inverse, here):
    """x [N, D] -> the row of each sorted slot's token [N * k, D]. `here`:
    how many of the sorted slots (the first) are on experts held."""
    return x[order // (order.shape[0] // x.shape[0])]


def _take_tokens_fwd(x, order, inverse, here):
    return _take_tokens(x, order, inverse, here), (inverse, here, x.shape[0])


def _take_tokens_bwd(res, g):
    inverse, here, n = res
    # a gather and a sum, not a scatter-add; the rows of slots no held
    # expert owns were never defined (grouped_matmul) and are left out
    g = jnp.where((inverse < here)[:, None], g[inverse], 0)
    return g.reshape(n, -1, g.shape[-1]).sum(axis=1), None, None, None


_take_tokens.defvjp(_take_tokens_fwd, _take_tokens_bwd)


@jax.custom_vjp
def _unsort(y, order, inverse, here):
    """y [N * k, D] in sorted order -> slot order, the undefined rows of
    slots no held expert owns as zeros."""
    return jnp.where((inverse < here)[:, None], y[inverse], 0)


def _unsort_fwd(y, order, inverse, here):
    return _unsort(y, order, inverse, here), (order, here)


def _unsort_bwd(res, g):
    order, here = res
    # rows past `here` get what their slots were handed: zeros times the
    # weights' cotangent, defined, and never read by a kernel
    return g[order], None, None, None


_unsort.defvjp(_unsort_fwd, _unsort_bwd)


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
    every expert. -> (y [N, D], stats [3] float32: slots routed, slots on
    experts held here, the fullest held expert's load over the mean). The
    row buffer is all N * k slots, so there is nothing to drop and no
    count of it."""
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
        inverse = jnp.zeros_like(order).at[order].set(
            jnp.arange(order.shape[0], dtype=jnp.int32))
        counts = jnp.bincount(key, length=e + 1).astype(jnp.int32)
        group_sizes = counts[:eh]
        here = jnp.sum(group_sizes)
    with jax.named_scope("moe.dispatch"):
        rows = _take_tokens(x, order, inverse, here)       # [N * k, D]
    with jax.named_scope("moe.experts"):
        up = grouped_matmul(rows, w_up, group_sizes, impl)
        hidden = activation(
            grouped_matmul(rows, w_gate, group_sizes, impl)) * up
        out = grouped_matmul(hidden.astype(x.dtype), w_down, group_sizes,
                             impl)
    with jax.named_scope("moe.combine"):
        slots = _unsort(out, order, inverse, here).reshape(n, top_k, d)
        y = jnp.sum(slots.astype(jnp.float32) * weight[..., None], axis=1)
    real_slots = jnp.sum(counts[:e])
    stats = jnp.stack([
        real_slots, here,
        jnp.max(group_sizes) * eh / jnp.maximum(here, 1),
    ]).astype(jnp.float32)
    return y.astype(x.dtype), stats
