"""Serving launcher: the train CLI's comm surface pointed at decode.

Batched prefill + decode (reduced configs run on host devices; full
configs are the dry-run's domain) with the compressed serving plane:
``--kv-bits`` switches the KV cache to packed codes + group scales,
``--stages N`` routes the hidden state through N-1 delta-coded pipeline
hops per token (`serving.delta`), and ``--continuous`` drives a
mixed-length request stream through the paged `serving.batcher`.

Communication knobs are ONE `repro.comm.CommConfig` — the same flags
(--mode/--fw-bits/--kv-bits/...) and ``--comm-config`` JSON as
`repro.launch.train`, and the resolved config is echoed back as JSON
(the round-trip surface).  ``--list-wires`` prints the same registry
table, serving planes included.

Examples:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
  python -m repro.launch.serve --arch gemma2-9b --smoke --batch 8 \\
      --prompt-len 32 --gen 16 --kv-bits 8
  python -m repro.launch.serve --smoke --stages 2 --mode aqsgd \\
      --fw-bits 4 --gen 12
  python -m repro.launch.serve --smoke --continuous --slots 4 --gen 8 \\
      --comm-config '{"mode": "aqsgd", "kv": {"bits": 8}}'
"""
from __future__ import annotations

import argparse
import time


def main():
    from repro.comm import config as comm_cli
    from repro.launch.train import print_wires

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2-9b")
    ap.add_argument("--smoke", action="store_true")
    comm_cli.add_cli_args(ap)
    ap.add_argument("--list-wires", action="store_true",
                    help="print the wire registry table and exit")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--data-par", type=int, default=1)
    ap.add_argument("--model-par", type=int, default=1)
    ap.add_argument("--stages", type=int, default=1,
                    help="pipeline stage groups for decode; >1 routes "
                         "the hidden state through delta-coded hops")
    ap.add_argument("--continuous", action="store_true",
                    help="serve a mixed-length request stream through "
                         "the continuous batcher instead of one "
                         "uniform batch")
    ap.add_argument("--slots", type=int, default=0,
                    help="batcher cache slots (default: --batch)")
    args = ap.parse_args()

    if args.list_wires:
        print_wires()
        return

    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro import env
    from repro.configs.base import get_config
    from repro.launch.mesh import make_debug_mesh
    from repro.models import model as Mo
    from repro.serving import (ContinuousBatcher, DeltaHopCodec, KVCodec,
                               quantize_caches)

    env.use_compile_cache()
    comm = comm_cli.from_args(args)
    print("comm:", comm.to_json())
    cfg = get_config(args.arch, smoke=args.smoke)
    kv_codec = KVCodec.from_comm(comm)
    hop = DeltaHopCodec.from_comm(comm) if args.stages > 1 else None
    if hop is not None:
        per_hop = hop.hop_bytes(args.batch, cfg.d_model)
        raw_hop = args.batch * cfg.d_model * 4
        print(f"decode hop [{comm.mode}]: {per_hop} B/token/boundary "
              f"x {args.stages - 1} boundaries (fp32 {raw_hop} B)")
    if kv_codec.bits:
        per_tok = kv_codec.stored_bytes(
            (1, 1, cfg.num_kv_heads, cfg.head_dim)) * 2 * cfg.num_layers
        raw_tok = 2 * cfg.num_layers * cfg.num_kv_heads * cfg.head_dim * 4
        print(f"kv cache: {per_tok} B/token stored "
              f"({kv_codec.bits}-bit; raw f32 {raw_tok} B)")

    key = jax.random.PRNGKey(0)
    params = Mo.init_params(cfg, key)
    cache_len = args.prompt_len + args.gen + (cfg.num_patches or 0)

    if args.continuous:
        slots = args.slots or args.batch
        bat = ContinuousBatcher(
            params, cfg, num_slots=slots, cache_len=cache_len,
            kv_codec=kv_codec, hop_codec=hop, num_stages=args.stages)
        rng = np.random.default_rng(1)
        t0 = time.time()
        for r in range(args.batch * 2):   # oversubscribe: forces evict+admit
            plen = int(rng.integers(4, args.prompt_len + 1))
            bat.submit(rng.integers(0, cfg.vocab_size, plen).tolist(),
                       max_new_tokens=args.gen)
        reqs = bat.run()
        dt = time.time() - t0
        n_tok = sum(len(r.tokens) for r in reqs)
        print(f"continuous: {len(reqs)} requests over {slots} slots, "
              f"{n_tok} tokens in {dt:.1f}s ({n_tok/dt:.1f} tok/s)")
        for r in reqs[:4]:
            print(f"  prompt[{len(r.prompt):3d}] -> {r.tokens[:8]}")
        return

    mesh = make_debug_mesh(args.data_par, args.model_par)
    caches = Mo.init_caches(cfg, args.batch, cache_len, jnp.float32)
    if kv_codec.bits:
        caches = quantize_caches(cfg, caches, kv_codec)
    if hop is not None:
        caches["hop_m"] = hop.init_state(args.stages - 1, args.batch,
                                         cfg.d_model)["m"]
    tokens = jax.random.randint(jax.random.PRNGKey(1),
                                (args.batch, args.prompt_len), 0,
                                cfg.vocab_size)
    extras = {}
    if cfg.family == "vlm":
        extras["patches"] = jax.random.normal(
            key, (args.batch, cfg.num_patches, cfg.d_model)) * 0.02
    if cfg.family == "audio":
        extras["frames"] = jax.random.normal(
            key, (args.batch, cfg.encoder_seq, cfg.d_model)) * 0.02

    kvc = kv_codec if kv_codec.bits else None
    bfn_p = hop.boundary_fn(prefill=True) if hop is not None else None
    bfn_d = hop.boundary_fn(prefill=False) if hop is not None else None
    with mesh:
        t0 = time.time()
        logits, caches = Mo.forward_with_caches(
            params, cfg, tokens, caches, logits_last_only=True,
            num_stages=args.stages, boundary_fn=bfn_p, kv_codec=kvc,
            **extras)
        logits.block_until_ready()
        t1 = time.time()
        print(f"prefill {args.batch}x{args.prompt_len}: {t1-t0:.2f}s")

        step = jax.jit(lambda p, c, t: Mo.forward_with_caches(
            p, cfg, t, c, logits_last_only=True, num_stages=args.stages,
            boundary_fn=bfn_d, kv_codec=kvc))
        out_tokens = []
        tok = jnp.argmax(logits[:, -1], axis=-1)[:, None]
        for i in range(args.gen):
            out_tokens.append(tok)
            logits, caches = step(params, caches, tok)
            if args.temperature > 0:
                tok = jax.random.categorical(
                    jax.random.fold_in(key, i),
                    logits[:, -1] / args.temperature)[:, None]
            else:
                tok = jnp.argmax(logits[:, -1], axis=-1)[:, None]
        jax.block_until_ready(tok)
        t2 = time.time()
        gen = jnp.concatenate(out_tokens, axis=1)
        print(f"decode {args.gen} tokens: {t2-t1:.2f}s "
              f"({args.gen*args.batch/(t2-t1):.1f} tok/s)")
        print("sample token ids:", gen[0][:12].tolist())


if __name__ == "__main__":
    main()
