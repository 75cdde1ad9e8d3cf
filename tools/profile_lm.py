"""Generation capture tool: compile the two paged KV-cache programs
(ISSUE 19) and the three NMT beam-decode programs (ISSUE 18) at their
committed audit configs and write the captures next to the committed
traces:

  tools/traces/lm_prefill_t1024_flash.hlo.txt.gz   bucketed prefill
      (full flash causal forward + page scatter + fused first top-k)
  tools/traces/lm_decode_b4.hlo.txt.gz             fused decode step
      (page gather -> 1-token forward -> in-place cache append ->
      argmax + score update, ONE dispatch per token)
  tools/traces/nmt_beam4_decode_b32{,_k8,_chunk8}.hlo.txt.gz
      attention-NMT beam search (beam 4, 32 rows of 32 source tokens,
      bf16 compute): the one-token jitted loop, the 8-token loop and
      the host rung's donated 8-step chunk program

plus, for the LM captures, a `.report.json` sibling per capture carrying the audit inputs
(`attn_impl`, `seq_len`, `donated_arg_buffers` — the two pool buffers
the append must alias in place). `tools/framework_lint.py hlo-audit
--write-audit` then pins each capture against its
tools/traces/audit_budgets.json policy: byte budgets, zero host
transfers inside the programs, the pool-donation check, and no [T,T]
materialization on the flash prefill at T=1024.

Compilation allocates no live model state beyond the toy-sized params
and the page pool (~8 MB/buffer), so the captures build on CPU — the
same no-TPU-needed discipline as tools/profile_longctx.py.

Usage: python tools/profile_lm.py [--out-dir tools/traces]
"""

import argparse
import os
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def write_lm_prefill_hlo(plm, bs, bucket, path):
    """Compile (never run) the bucketed LM prefill program at the
    committed capture config and write HLO + report sibling — the
    audit pins: flash path (no [T,T] at T=1024), zero host transfers,
    and the donated pool buffers (cache-append aliasing)."""
    import gzip
    import json

    import jax.numpy as jnp

    spec = plm.spec
    ps = plm.cache.page_size
    pool_k, pool_v = plm.cache.ensure_pool()
    prog = plm._prefill_program(bs, bucket)
    n_pages = bucket // ps
    compiled = prog.lower(
        plm.params, pool_k, pool_v,
        jnp.zeros((bs, bucket), jnp.int32),
        jnp.full((bs,), bucket, jnp.int32),
        jnp.arange(bs * n_pages, dtype=jnp.int32).reshape(
            bs, n_pages
        ),
    ).compile()
    with gzip.open(path, "wt") as f:
        f.write(compiled.as_text())
    ca = compiled.cost_analysis()
    ca = ca[0] if isinstance(ca, list) else ca
    report = {
        "model": "decoding.kv_cache prefill program (full causal "
                 "forward + page scatter + fused first top-k)",
        "attn_impl": spec.attn_impl,
        "batch_size": bs,
        "seq_len": bucket,
        "d_model": spec.d_model,
        "heads": spec.num_heads,
        "layers": spec.num_layers,
        "page_size": ps,
        "xla_flops": ca.get("flops", 0),
        "xla_bytes_accessed": ca.get("bytes accessed", 0),
        # the donation audit's contract: the two pool buffers (K, V)
        # must appear in input_output_alias — the cache append is
        # in place, not a copy
        "donated_arg_buffers": 2,
    }
    with open(path.replace(".hlo.txt.gz", ".report.json"), "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")


def write_lm_decode_hlo(plm, bs, path):
    """Compile the fused per-token decode program (gather pages ->
    1-token forward -> in-place append -> argmax+score) and write
    HLO + report — the single-dispatch-per-token program that retires
    ROADMAP residual 2(c)."""
    import gzip
    import json

    import jax.numpy as jnp

    spec = plm.spec
    maxp = plm.cache.max_pages_per_seq
    ps = plm.cache.page_size
    pool_k, pool_v = plm.cache.ensure_pool()
    prog = plm._decode_program(bs)
    compiled = prog.lower(
        plm.params, pool_k, pool_v,
        jnp.zeros((bs,), jnp.int32),
        jnp.full((bs,), ps, jnp.int32),
        jnp.zeros((bs, maxp), jnp.int32),
        jnp.zeros((bs,), jnp.float32),
        jnp.zeros((bs,), bool),
    ).compile()
    with gzip.open(path, "wt") as f:
        f.write(compiled.as_text())
    ca = compiled.cost_analysis()
    ca = ca[0] if isinstance(ca, list) else ca
    report = {
        "model": "decoding.kv_cache fused decode step (forward + "
                 "top-k + cache append + score update, one dispatch)",
        "batch_size": bs,
        "context_len": maxp * ps,
        "d_model": spec.d_model,
        "heads": spec.num_heads,
        "layers": spec.num_layers,
        "page_size": ps,
        "xla_flops": ca.get("flops", 0),
        "xla_bytes_accessed": ca.get("bytes accessed", 0),
        "donated_arg_buffers": 2,
    }
    with open(path.replace(".hlo.txt.gz", ".report.json"), "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")


def write_lm_captures(out_dir):
    """The two committed LM generation captures (ISSUE 19) at their
    audited configs: the T=1024 flash prefill and the b=4 fused
    decode step over a 1024-slot page context. Compile-only, so the
    writer runs on CPU; tools/profile_lm.py is the standalone CLI."""
    import jax

    from paddle_tpu.decoding.kv_cache import PagedKVCache, PagedLM
    from paddle_tpu.models.lm import LMSpec, lm_init_params

    spec = LMSpec(vocab=2048, d_model=256, num_heads=4, num_layers=2,
                  attn_impl="flash")
    params = lm_init_params(spec, jax.random.key(0))
    cache = PagedKVCache(spec, num_pages=256, page_size=16,
                         max_pages_per_seq=64)
    plm = PagedLM(spec, params, cache)
    p1 = os.path.join(out_dir, "lm_prefill_t1024_flash.hlo.txt.gz")
    write_lm_prefill_hlo(plm, 4, 1024, p1)
    p2 = os.path.join(out_dir, "lm_decode_b4.hlo.txt.gz")
    write_lm_decode_hlo(plm, 4, p2)
    return [p1, p2]


def write_decode_hlo(dec, params, statics, boots, path):
    """Dump the compiled decode program's HLO text (gzipped) for
    tools/trace_attribution.py's HLO-capture mode — the per-iteration
    byte accounting behind the beam-decode floor analysis (ROADMAP
    5a / PERF.md round 8). Works on any backend: compilation needs no
    device execution."""
    import gzip

    static_feed, init_carry_mem, b = dec.prepare(statics, boots)
    run = dec._decode_program()
    txt = run.lower(
        params, static_feed, init_carry_mem, b
    ).compile().as_text()
    with gzip.open(path, "wt") as f:
        f.write(txt)
    return path


def write_chunk_hlo(dec, params, statics, boots, n_steps, path):
    """Dump the host rung's K-step chunk program (ISSUE 18:
    `BeamSearchDecoder._chunk_step_program` — the serving ladder's
    per-chunk dispatch unit) as gzipped compiled HLO. This is the
    capture whose audit policy checks DONATION: the carried memories
    are donated into the program and must come back aliased."""
    import gzip

    import jax.numpy as jnp

    from paddle_tpu.beam_search import NEG_INF

    static_feed, mems, b = dec.prepare(statics, boots)
    prog = dec._chunk_step_program(b, n_steps)
    k = dec.k
    words = jnp.full((b, k), dec.bos_id, jnp.int32)
    scores = jnp.full((b, k), NEG_INF, jnp.float32).at[:, 0].set(0.0)
    fin = jnp.zeros((b, k), bool)
    txt = prog.lower(
        params, static_feed, mems, words, scores, fin, jnp.int32(0)
    ).compile().as_text()
    with gzip.open(path, "wt") as f:
        f.write(txt)
    return path


def write_decode_captures(out_dir):
    """The three committed NMT beam-decode captures at their audited
    config, under the bf16 compute they were baselined with."""
    import jax
    import numpy as np

    from paddle_tpu.core import flags as _flags
    from paddle_tpu.core.arg import id_arg
    from paddle_tpu.models.text import (
        seq2seq_attention,
        seq2seq_attention_decoder,
    )
    from paddle_tpu.network import Network

    bs, t_src, beam, max_len = 32, 32, 4, 32
    hidden, vocab, emb = 512, 30000, 512
    _flags.set_flag("matmul_precision", "bfloat16")
    jax.config.update("jax_default_prng_impl", "rbg")
    net = Network(seq2seq_attention(
        src_vocab=vocab, trg_vocab=vocab, emb_dim=emb, hidden=hidden
    ))
    params = net.init_params(jax.random.key(0))
    rng = np.random.default_rng(0)
    src = rng.integers(2, vocab, (bs, t_src)).astype(np.int32)
    lens = np.full((bs,), t_src, np.int32)
    enc_outs, _ = net.forward(
        params, {"src": id_arg(src, lens)},
        outputs=["enc", "dec_boot"],
    )
    statics = [enc_outs["enc"]]
    boots = {"dec_state": enc_outs["dec_boot"].value}

    def decoder(**kw):
        return seq2seq_attention_decoder(
            trg_vocab=vocab, emb_dim=emb, hidden=hidden, bos_id=0,
            eos_id=1, beam_size=beam, max_length=max_len, **kw
        )

    stem = os.path.join(out_dir, "nmt_beam4_decode_b32")
    dec_k = decoder(tokens_per_dispatch=8)
    return [
        write_decode_hlo(decoder(), params, statics, boots,
                         stem + ".hlo.txt.gz"),
        write_decode_hlo(dec_k, params, statics, boots,
                         stem + "_k8.hlo.txt.gz"),
        write_chunk_hlo(dec_k, params, statics, boots, 8,
                        stem + "_chunk8.hlo.txt.gz"),
    ]


def main():
    ap = argparse.ArgumentParser(
        description="write the committed generation captures"
    )
    ap.add_argument("--out-dir", default="tools/traces")
    args = ap.parse_args()

    os.makedirs(args.out_dir, exist_ok=True)
    for path in (write_lm_captures(args.out_dir)
                 + write_decode_captures(args.out_dir)):
        print(path)
    return 0


if __name__ == "__main__":
    sys.exit(main())
