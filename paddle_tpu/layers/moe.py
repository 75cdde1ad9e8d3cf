"""MoE layer: sparsely-activated expert FFN. One layer type, two routings.

With `top_k` in its attrs it is the expert layer of a present-day decoder
block (models/mellum.py, models/kimi.py): top-k routing over ALL
`num_experts` (`scoring_func` "softmax", or "sigmoid" with
`routed_scaling_factor`; `topk_method` "noaux_tc" chooses by score plus the
selection bias `e_score_correction_bias`, a float32 constant of the job that
no gradient reaches and the optimizer leaves alone), of which the layer
HOLDS a contiguous share (`held`: first index and count, as one chip of
an expert-parallel layer does), gated
(SiLU) experts, renormalised weights, and no dropped token whatever the
imbalance (ops/moe.py `dropless_moe`: a sort, grouped matrix products, a
weighted sum a token, each pass no longer than the slots held). It computes
its own experts' part of the result; what absent experts would add is left
out, and no code stands in for the exchange. The router's weights stay the float32 masters under the bfloat16
policy (`float32_params`): its logits are a float32 product. Its extra
output `<name>@stats` carries the routing counts the trainer publishes at
its fence (`moe.slots`, `moe.slots_here`, `moe.load_max_over_mean`, and
`moe.rows_moved`: the rows the dispatch moved, the held slots rounded up to
a chunk; over `moe.slots` it is the part of the buffer that was worked, and
1.0 means the held count saved nothing); there is no count of dropped slots,
because the row buffer is all the slots and nothing on this path can drop
one.

Without `top_k` it is the older Switch-style layer that
tests/test_pipeline_moe.py drives: top-1, a fixed capacity with tokens
dropped over it, a dense dispatch tensor, and its load-balancing loss as
the extra output `<name>@aux` that the DSL wires into a sum_cost. Kept for
those tests only; nothing new should route through it.

Expert weights carry an "expert" leading dim that parallel/sharding can
place on the mesh model axis for EP.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu.core.arg import Arg
from paddle_tpu.core.registry import LAYERS
from paddle_tpu.layers.base import Layer, Spec
from paddle_tpu.ops import moe as moe_ops
from paddle_tpu.ops import activations


@LAYERS.register("moe")
class MoELayer(Layer):
    """attrs: num_experts, hidden (expert FFN width), capacity_factor,
    expert_act. size = output dim (== input dim). Params: router w0
    [D, E]; experts w_in [E, D, H], w_out [E, H, D]."""

    # the dropless path's router (the capacity path's is `w0`) and its
    # selection bias
    float32_params = ("router", "e_score_correction_bias")

    @property
    def dropless(self) -> bool:
        return "top_k" in self.conf.attrs

    def _held(self):
        a = self.conf.attrs
        first, count = a.get("held") or (0, a["num_experts"])
        assert 0 <= first and first + count <= a["num_experts"], a["held"]
        return int(first), int(count)

    def _build_dropless(self, s):
        """Router [D, E] over all the experts (with `topk_method`
        "noaux_tc" its selection bias [E] beside it); w_gate, w_up
        [Eh, D, H] and w_down [Eh, H, D] of the Eh held."""
        d = s.size
        a = self.conf.attrs
        _, eh = self._held()
        H = a["hidden"]
        slots = {"router": (d, a["num_experts"]), "w_gate": (eh, d, H),
                 "w_up": (eh, d, H), "w_down": (eh, H, d)}
        pcs = {}
        for slot, dims in slots.items():
            pc = self.weight_conf(0, dims)
            pc.name = f"_{self.name}.{slot}"
            if slot != "router":
                pc.expert_sharded = True
                if pc.initial_std is None:     # one expert's fan-in
                    pc.initial_std = 1.0 / (dims[1] ** 0.5)
            pcs[slot] = pc
        if a.get("topk_method") == "noaux_tc":
            pc = self.weight_conf(0, (a["num_experts"],))
            pc.name = f"_{self.name}.e_score_correction_bias"
            pc.is_static = True
            pcs["e_score_correction_bias"] = pc
        self._spec = s
        return s, pcs

    def build(self, in_specs):
        (s,) = in_specs
        if self.dropless:
            return self._build_dropless(s)
        d = s.size
        a = self.conf.attrs
        E = a["num_experts"]
        H = a.get("hidden") or 4 * d
        pcs = {
            "w0": self.weight_conf(0, (d, E)),
            "w_in": self.weight_conf(0, (E, d, H)),
            "w_out": self.weight_conf(0, (E, H, d)),
        }
        # distinct auto-names for the three slots
        pcs["w_in"].name = pcs["w0"].name + "_in"
        pcs["w_out"].name = pcs["w0"].name + "_out"
        pcs["w_in"].expert_sharded = True
        pcs["w_out"].expert_sharded = True
        # per-expert fan-in: each token multiplies ONE [D,H] slice, so
        # std is 1/sqrt(D) (init_parameter's prod(dims[:-1]) would give
        # 1/sqrt(E*D) — E-times too small). User-set std wins.
        if pcs["w_in"].initial_std is None:
            pcs["w_in"].initial_std = 1.0 / (d ** 0.5)
        if pcs["w_out"].initial_std is None:
            pcs["w_out"].initial_std = 1.0 / (H ** 0.5)
        self._spec = s
        return s, pcs

    def extra_output_specs(self):
        if self.dropless:
            return {f"{self.name}@stats": Spec(dim=(4,))}
        return {f"{self.name}@aux": Spec(dim=(1,))}

    @property
    def stats_output(self):
        """The extra output that carries the routing counts (`Network`
        lists it in `stat_outputs`; the trainer's fence publishes it)."""
        return f"{self.name}@stats" if self.dropless else None

    def publish_stats(self, values, registry) -> None:
        slots, here, load, moved = (float(v) for v in values)
        registry.counter("moe.slots").inc(slots, layer=self.name)
        registry.counter("moe.slots_here").inc(here, layer=self.name)
        registry.counter("moe.rows_moved").inc(moved, layer=self.name)
        registry.gauge("moe.load_max_over_mean").set(load, layer=self.name)

    def _forward_dropless(self, params, x):
        a = self.conf.attrs
        v = x.value
        flat = v.reshape(-1, v.shape[-1])
        # one trace serves the layers of one shape (a decoder's are alike):
        # jit keeps it by the function and these static arguments
        y, stats = jax.jit(moe_ops.dropless_moe, static_argnames=(
            "top_k", "held_first", "norm_topk", "scoring", "routed_scale",
            "activation"))(
            flat, params["router"], params["w_gate"], params["w_up"],
            params["w_down"], top_k=a["top_k"], held_first=self._held()[0],
            norm_topk=a.get("norm_topk", True),
            scoring=a.get("scoring_func", "softmax"),
            select_bias=params.get("e_score_correction_bias"),
            routed_scale=a.get("routed_scaling_factor", 1.0),
            activation=activations.get(a.get("expert_act", "silu")),
            token_mask=x.mask(jnp.float32).reshape(-1) if x.is_seq else None,
        )
        self._extra_outs = {
            f"{self.name}@stats": Arg(value=stats.reshape(1, 4))
        }
        return Arg(value=y.reshape(v.shape), seq_lens=x.seq_lens)

    def forward(self, params, inputs, ctx):
        (x,) = inputs
        if self.dropless:
            return self._forward_dropless(params, x)
        a = self.conf.attrs
        act = activations.get(a.get("expert_act", "relu"))
        v = x.value
        lead = v.shape[:-1]
        flat = v.reshape(-1, v.shape[-1])
        # padded tokens are excluded from routing itself (capacity and
        # balance statistics), not just output-masked
        token_mask = (
            x.mask(v.dtype).reshape(-1) if x.is_seq else None
        )
        y, aux = moe_ops.moe_ffn(
            flat,
            params["w0"],
            params["w_in"],
            params["w_out"],
            capacity_factor=a.get("capacity_factor", 1.25),
            activation=act,
            token_mask=token_mask,
        )
        y = y.reshape(lead + (-1,))
        self._extra_outs = {
            f"{self.name}@aux": Arg(value=jnp.broadcast_to(aux, (1, 1)))
        }
        return Arg(value=y, seq_lens=x.seq_lens)
