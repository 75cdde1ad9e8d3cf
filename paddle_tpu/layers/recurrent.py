"""Recurrent layers: simple RNN, LSTM, GRU over packed [B,T,*] batches.

Reference: gserver/layers/{RecurrentLayer,LstmLayer,GatedRecurrentLayer}.cpp
with fused CUDA cells (cuda/src/hl_cuda_lstm.cu, hl_gpu_gru.cuh) and
SequenceToBatch reordering (SequenceToBatch.h) so unequal-length sequences
advance together without padding.

TPU-first redesign: `lax.scan` over the time axis of a dense [B,T,*] batch.
Variable lengths are handled by masked state carry — at a padded timestep
the hidden/cell state is carried through unchanged and the output is zeroed,
which reproduces SequenceToBatch semantics exactly (padding can never leak
into real steps). The big input projection x@W (size -> 4h/3h) is done by
the *preceding* layer, as in the reference where lstmemory expects a
4*size input; the per-step matmul here is only h @ W_rec, which XLA fuses
into one MXU call per step inside the scan.

Gate order (matching the reference's buffer layout): LSTM = [i, f, g, o],
GRU = [u, r, c]. LSTM bias holds 4h gate biases + 3h peephole weights
(Wci, Wcf, Wco), total 7h, as in LstmLayer.cpp.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu import ops
from paddle_tpu.core.arg import Arg
from paddle_tpu.core.registry import LAYERS
from paddle_tpu.layers.base import Layer, Spec
from paddle_tpu.ops import activations
from paddle_tpu.ops import sequence_ops as sops


def _use_fused(bsz=None, t_max=None, h=None, mult=4) -> bool:
    """Fused Pallas cell policy: explicit flag only.

    Round-3 interleaved A/B measurement (the pre-chip harness's
    fused-vs-scan LSTM row: both arms compiled+warmed, alternating
    timing windows, min per arm — immune to the host-stall bias that
    produced round 2's contradictory numbers) shows XLA's
    lax.scan lowering BEATS the fused Pallas kernels on v5e at every
    tested shape, training AND inference:
      train  scan/fused: bs128 h256 0.85x, bs128 h512 1.04x (noise),
             bs128 h1280 0.81x, bs256 h256 0.59x, bs256 h512 0.64x
      fwd    bs128 h256 0.92x, bs128 h512 0.87x, bs256 h512 0.52x
    So auto NEVER engages the kernels; they remain available for
    explicit opt-in (flags.set_flag('use_pallas_rnn', True)) and are
    correctness-tested in test_pallas_kernels.py. The capability match
    for cuda/src/hl_cuda_lstm.cu is the kernels' existence; the perf
    match on TPU is the scan+XLA path.

    The shape parameters are intentionally retained (unused) so call
    sites keep passing them — if a future XLA/Mosaic shift flips the
    A/B (no cell measures it: ROADMAP Design 5), the policy slots
    back in without touching callers.

    Round-6 verdict (ROADMAP 5a, PERF.md "fused-RNN family retired"):
    the family is formally RETIRED as a production path. The GRU
    backward was never landed (it recomputes through the scan
    reference, so fused-GRU training pays kernel forward + scan
    backward), and the completed LSTM pair loses to the scan at every
    measured shape — engaging the flag now warns DeprecationWarning
    once per process. The kernels stay in-tree, correctness-tested, as
    the hl_cuda_lstm.cu capability match and the A/B tripwire arm."""
    from paddle_tpu.core.flags import get_flag

    v = get_flag("use_pallas_rnn")
    if v is not None:
        if bool(v) and not _WARNED_FUSED_OPTIN:
            import warnings

            _WARNED_FUSED_OPTIN.append(True)
            warnings.warn(
                "use_pallas_rnn=True engages the RETIRED fused Pallas "
                "RNN path: measured slower than XLA lax.scan at every "
                "tested shape (PERF.md), and GRU has no fused backward "
                "(training recomputes through the scan). Kept for "
                "kernel A/B testing only.",
                DeprecationWarning,
                stacklevel=3,
            )
        return bool(v)
    return False


# once-per-process latch: a caller that flips the flag per timing
# window must not get a warning per engaged forward
_WARNED_FUSED_OPTIN: list = []


def _scan_rnn(step, x_btd, seq_lens, init_carry, reverse=False):
    """Run `step(carry, x_t, m_t) -> (carry, y_t)` over time with masked
    carry. x_btd: [B,T,D]. Returns y: [B,T,H]."""
    if reverse:
        x_btd = sops.reverse_seq(x_btd, seq_lens)
    t = x_btd.shape[1]
    mask_bt = (
        jnp.arange(t, dtype=jnp.int32)[None, :] < seq_lens[:, None]
    ).astype(x_btd.dtype)
    xs = (x_btd.swapaxes(0, 1), mask_bt.swapaxes(0, 1))  # time-major

    def body(carry, inp):
        x_t, m_t = inp
        new_carry, y_t = step(carry, x_t)
        m = m_t[:, None]
        new_carry = jax.tree_util.tree_map(
            lambda n, o: m * n + (1.0 - m) * o, new_carry, carry
        )
        return new_carry, y_t * m

    _, ys = lax.scan(body, init_carry, xs)
    y = ys.swapaxes(0, 1)
    if reverse:
        y = sops.reverse_seq(y, seq_lens)
    return y


@LAYERS.register("recurrent")
class RecurrentLayer(Layer):
    """h_t = act(x_t + h_{t-1} @ W) (gserver/layers/RecurrentLayer.cpp).
    attrs: reversed."""

    def build(self, in_specs):
        (s,) = in_specs
        h = self.conf.size
        if not h:
            # raw configs omit size; the reference defaults it to the
            # input width (config_parser RecurrentLayer set_layer_size)
            h = self.conf.size = s.size
        assert s.size == h, "recurrent layer input must equal size"
        pcs = {"w0": self.weight_conf(0, (h, h))}
        b = self.bias_conf((h,))
        if b is not None:
            pcs["b"] = b
        return Spec(dim=(h,), is_seq=True), pcs

    def forward(self, params, inputs, ctx):
        (arg,) = inputs
        act = self.activation() if self.conf.active_type else jnp.tanh
        w = params["w0"]
        b = params.get("b", 0.0)

        def step(h_prev, x_t):
            h = act(x_t + jnp.dot(h_prev, w) + b)
            return h, h

        bsz = arg.value.shape[0]
        h0 = jnp.zeros((bsz, self.conf.size), arg.value.dtype)
        y = _scan_rnn(
            step, arg.value, arg.seq_lens, h0, self.conf.attrs.get("reversed", False)
        )
        return Arg(value=y, seq_lens=arg.seq_lens)


@LAYERS.register("lstmemory", "lstm")
class LstmLayer(Layer):
    """LSTM with peepholes (gserver/layers/LstmLayer.cpp,
    cuda/src/hl_cuda_lstm.cu). Input: [B,T,4h] pre-projected. Params:
    W_rec [h,4h], bias [7h] = gate biases [4h] + peepholes Wci/Wcf/Wco [3h].
    attrs: reversed, active_gate_type (sigmoid), active_state_type (tanh).
    conf.active_type is the candidate/output activation (default tanh)."""

    def build(self, in_specs):
        (s,) = in_specs
        h = self.conf.size
        assert s.size == 4 * h, f"lstmemory input must be 4*size, got {s.size}"
        pcs = {"w0": self.weight_conf(0, (h, 4 * h))}
        b = self.bias_conf((7 * h,))
        if b is not None:
            pcs["b"] = b
        return Spec(dim=(h,), is_seq=True), pcs

    def forward(self, params, inputs, ctx):
        (arg,) = inputs
        h = self.conf.size
        act = activations.get(self.conf.active_type or "tanh")
        gate_act = activations.get(self.conf.attrs.get("active_gate_type", "sigmoid"))
        state_act = activations.get(self.conf.attrs.get("active_state_type", "tanh"))
        w = params["w0"]
        if "b" in params:
            gb = params["b"][: 4 * h]
            wci = params["b"][4 * h : 5 * h]
            wcf = params["b"][5 * h : 6 * h]
            wco = params["b"][6 * h : 7 * h]
        else:
            gb = jnp.zeros((4 * h,), arg.value.dtype)
            wci = wcf = wco = jnp.zeros((h,), arg.value.dtype)

        default_acts = (
            (self.conf.active_type or "tanh") == "tanh"
            and self.conf.attrs.get("active_gate_type", "sigmoid") == "sigmoid"
            and self.conf.attrs.get("active_state_type", "tanh") == "tanh"
        )
        if default_acts and _use_fused(
            arg.value.shape[0], arg.value.shape[1], h, mult=4
        ):
            from paddle_tpu.ops import pallas_rnn

            x = arg.value
            rev = self.conf.attrs.get("reversed", False)
            if rev:
                x = sops.reverse_seq(x, arg.seq_lens)
            y = pallas_rnn.lstm_fused(
                x, w, gb, wci, wcf, wco, arg.seq_lens,
                ops.pallas_interpret(),
            )
            if rev:
                y = sops.reverse_seq(y, arg.seq_lens)
            return Arg(value=y, seq_lens=arg.seq_lens)

        def step(carry, x_t):
            h_prev, c_prev = carry
            g = x_t + jnp.dot(h_prev, w) + gb
            gi, gf, gg, go = jnp.split(g, 4, axis=-1)
            i = gate_act(gi + wci * c_prev)
            f = gate_act(gf + wcf * c_prev)
            cand = act(gg)
            c = f * c_prev + i * cand
            o = gate_act(go + wco * c)
            out = o * state_act(c)
            return (out, c), out

        bsz = arg.value.shape[0]
        zeros = jnp.zeros((bsz, h), arg.value.dtype)
        y = _scan_rnn(
            step,
            arg.value,
            arg.seq_lens,
            (zeros, zeros),
            self.conf.attrs.get("reversed", False),
        )
        return Arg(value=y, seq_lens=arg.seq_lens)


@LAYERS.register("gated_recurrent", "grumemory", "gru")
class GruLayer(Layer):
    """GRU (gserver/layers/GatedRecurrentLayer.cpp, hl_gpu_gru.cuh).
    Input: [B,T,3h] pre-projected as [update, reset, candidate].
    h_t = u ⊙ h_{t-1} + (1-u) ⊙ c_t. attrs: reversed."""

    def build(self, in_specs):
        (s,) = in_specs
        h = self.conf.size
        assert s.size == 3 * h, f"grumemory input must be 3*size, got {s.size}"
        pcs = {"w0": self.weight_conf(0, (h, 2 * h)), "w_c": self.weight_conf(0, (h, h))}
        pcs["w_c"].name = f"_{self.name}.wc"
        b = self.bias_conf((3 * h,))
        if b is not None:
            pcs["b"] = b
        return Spec(dim=(h,), is_seq=True), pcs

    def forward(self, params, inputs, ctx):
        (arg,) = inputs
        h = self.conf.size
        act = activations.get(self.conf.active_type or "tanh")
        gate_act = activations.get(self.conf.attrs.get("active_gate_type", "sigmoid"))
        w_g = params["w0"]  # [h, 2h] for update+reset
        w_c = params["w_c"]  # [h, h] candidate
        b = params.get("b", jnp.zeros((3 * h,), arg.value.dtype))

        default_acts = (
            (self.conf.active_type or "tanh") == "tanh"
            and self.conf.attrs.get("active_gate_type", "sigmoid") == "sigmoid"
        )
        if default_acts and _use_fused(
            arg.value.shape[0], arg.value.shape[1], h, mult=3
        ):
            from paddle_tpu.ops import pallas_rnn

            x = arg.value
            rev = self.conf.attrs.get("reversed", False)
            if rev:
                x = sops.reverse_seq(x, arg.seq_lens)
            y = pallas_rnn.gru_fused(
                x, w_g, w_c, b, arg.seq_lens,
                ops.pallas_interpret(),
            )
            if rev:
                y = sops.reverse_seq(y, arg.seq_lens)
            return Arg(value=y, seq_lens=arg.seq_lens)

        def step(h_prev, x_t):
            xu, xr, xc = jnp.split(x_t + b, 3, axis=-1)
            gur = jnp.dot(h_prev, w_g)
            u = gate_act(xu + gur[..., :h])
            r = gate_act(xr + gur[..., h:])
            c = act(xc + jnp.dot(r * h_prev, w_c))
            out = u * h_prev + (1.0 - u) * c
            return out, out

        bsz = arg.value.shape[0]
        h0 = jnp.zeros((bsz, h), arg.value.dtype)
        y = _scan_rnn(
            step, arg.value, arg.seq_lens, h0, self.conf.attrs.get("reversed", False)
        )
        return Arg(value=y, seq_lens=arg.seq_lens)


@LAYERS.register("mdlstm", "mdlstmemory")
class MDLstmLayer(Layer):
    """2-D multi-dimensional LSTM (gserver/layers/MDLstmLayer.cpp):
    each grid cell takes the hidden/cell states of its row- and
    column-predecessors, with ONE shared recurrent weight applied to
    every neighbor's output (MDLstmLayer.cpp:547-561) and a forget gate
    PER dimension (forwardGate2OutputSequence, MDLstmLayer.cpp:475).

    Input: [B, H, W, 5h] pre-projected grid (gate layout
    [i | f_row | f_col | g | o], the (3+D)*size projection of the
    reference with D=2). Output [B, H, W, h]. Missing neighbors at the
    grid edges contribute nothing — realized exactly by zero boundary
    states. attrs: directions = (bool, bool) per dim, True = ascending
    scan (CoordIterator directions_); active_gate_type/
    active_state_type as in lstmemory. Params: w0 [h, 5h] shared
    recurrent weight; bias [5h gates + h wci + 2h wcf + h wco = 9h].

    TPU-first: lax.scan over rows with an inner lax.scan over columns
    (the reference's CoordIterator walk, compiled); grids are dense
    [H, W] — the nested-sequence packaging of the reference collapses
    to the image layout here."""

    def build(self, in_specs):
        (s,) = in_specs
        h = self.conf.size
        gh, gw, gc = s.dim
        assert gc == 5 * h, (
            f"mdlstm input must be (3+2)*size wide, got {gc} != {5 * h}"
        )
        self._grid = (gh, gw)
        pcs = {"w0": self.weight_conf(0, (h, 5 * h))}
        b = self.bias_conf((9 * h,))
        if b is not None:
            pcs["b"] = b
        return Spec(dim=(gh, gw, h), is_seq=s.is_seq), pcs

    def forward(self, params, inputs, ctx):
        (arg,) = inputs
        h = self.conf.size
        gh, gw = self._grid
        act = activations.get(self.conf.active_type or "tanh")
        gate_act = activations.get(
            self.conf.attrs.get("active_gate_type", "sigmoid")
        )
        state_act = activations.get(
            self.conf.attrs.get("active_state_type", "tanh")
        )
        dirs = self.conf.attrs.get("directions", (True, True))
        w = params["w0"]
        if "b" in params:
            gb = params["b"][: 5 * h]
            wci = params["b"][5 * h : 6 * h]
            wcf_r = params["b"][6 * h : 7 * h]
            wcf_c = params["b"][7 * h : 8 * h]
            wco = params["b"][8 * h : 9 * h]
        else:
            z = jnp.zeros((h,), arg.value.dtype)
            gb = jnp.zeros((5 * h,), arg.value.dtype)
            wci = wcf_r = wcf_c = wco = z

        x = arg.value.reshape(
            (arg.value.shape[0],) + (gh, gw, 5 * h)
        )
        # descending directions scan by flipping in, flipping back out
        if not dirs[0]:
            x = x[:, ::-1]
        if not dirs[1]:
            x = x[:, :, ::-1]
        bsz = x.shape[0]

        def cell(x_ij, h_top, c_top, h_left, c_left):
            pre = (
                x_ij
                + jnp.dot(h_top + h_left, w)
                + gb
            )
            ig = gate_act(pre[:, :h] + (c_top + c_left) * wci)
            f_r = gate_act(pre[:, h : 2 * h] + c_top * wcf_r)
            f_c = gate_act(pre[:, 2 * h : 3 * h] + c_left * wcf_c)
            g = act(pre[:, 3 * h : 4 * h])
            c = f_r * c_top + f_c * c_left + ig * g
            o = gate_act(pre[:, 4 * h :] + c * wco)
            return o * state_act(c), c

        zrow = jnp.zeros((bsz, gw, h), x.dtype)

        def row_step(carry, x_row):
            h_top_row, c_top_row = carry  # [B, W, h]
            zcol = jnp.zeros((bsz, h), x.dtype)

            def col_step(cc, inp):
                h_left, c_left = cc
                x_ij, h_t, c_t = inp
                out, c = cell(x_ij, h_t, c_t, h_left, c_left)
                return (out, c), (out, c)

            _, (h_row, c_row) = jax.lax.scan(
                col_step,
                (zcol, zcol),
                (
                    x_row.swapaxes(0, 1),
                    h_top_row.swapaxes(0, 1),
                    c_top_row.swapaxes(0, 1),
                ),
            )
            h_row = h_row.swapaxes(0, 1)
            c_row = c_row.swapaxes(0, 1)
            return (h_row, c_row), h_row

        _, ys = jax.lax.scan(
            row_step, (zrow, zrow), x.swapaxes(0, 1)
        )
        y = ys.swapaxes(0, 1)  # [B, H, W, h]
        if not dirs[0]:
            y = y[:, ::-1]
        if not dirs[1]:
            y = y[:, :, ::-1]
        return Arg(value=y, seq_lens=arg.seq_lens)
