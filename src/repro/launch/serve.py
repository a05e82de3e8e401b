"""Serving driver: continuous-batching engine demo.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b \
        --requests 16 --max-new 24

    # chunked decode: amortize dispatch over 8 tokens per engine step
    PYTHONPATH=src python -m repro.launch.serve --chunk 8

    # A/B the old per-slot host-sampling path
    PYTHONPATH=src python -m repro.launch.serve --engine legacy

    # paged KV cache: pool pages + prefix sharing (HBM ~ live tokens)
    PYTHONPATH=src python -m repro.launch.serve --engine paged --page-size 16

    # lossless speculative decoding: n-gram drafts, one verify dispatch
    PYTHONPATH=src python -m repro.launch.serve --spec-k 4

    # ... or draft with a smaller same-vocab model
    PYTHONPATH=src python -m repro.launch.serve --spec-k 4 --draft qwen1.5-4b

    # the published config (default: the reduced smoke config)
    PYTHONPATH=src python -m repro.launch.serve --full --engine paged
"""
from __future__ import annotations

import argparse
import time
from typing import List, Optional

import jax
import numpy as np

from repro.configs import get_config, reduced
from repro.launch import compile_cache
from repro.models import build_model
from repro.serve import Request, ServeEngine


def main(argv: Optional[List[str]] = None) -> None:
    """Serve a synthetic request burst and print its throughput."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--full", action="store_true",
                    help="serve the published config (and --draft's) "
                         "instead of the reduced one")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--engine", default="fused",
                    choices=["fused", "legacy", "paged"],
                    help="fused on-device sampling, the per-slot "
                         "baseline, or the paged KV cache")
    ap.add_argument("--chunk", type=int, default=1,
                    help="tokens decoded per dispatch (lax.scan chunk)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page (engine=paged; power of two)")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative drafts per verify round (0 = off)")
    ap.add_argument("--ngram-n", type=int, default=3,
                    help="n-gram order for the prompt-lookup proposer")
    ap.add_argument("--draft", default="",
                    help="draft model arch name (same vocab); empty = "
                         "n-gram proposer")
    args = ap.parse_args(argv)
    compile_cache.enable()

    scale = (lambda c: c) if args.full else reduced
    cfg = scale(get_config(args.arch))
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(args.seed))
    draft = dparams = None
    if args.draft:
        dcfg = scale(get_config(args.draft))
        draft = build_model(dcfg)
        dparams, _ = draft.init(jax.random.PRNGKey(args.seed + 1))
    engine = ServeEngine(model, params, max_batch=args.max_batch,
                         max_seq=args.prompt_len + args.max_new + 8,
                         engine=args.engine, decode_chunk=args.chunk,
                         page_size=args.page_size, spec_k=args.spec_k,
                         spec_ngram_n=args.ngram_n, draft=draft,
                         draft_params=dparams)
    rng = np.random.default_rng(args.seed)
    for i in range(args.requests):
        engine.submit(Request(
            uid=i,
            prompt=rng.integers(1, cfg.vocab_size, args.prompt_len).astype(np.int32),
            max_new_tokens=args.max_new,
            temperature=args.temperature,
        ))
    t0 = time.time()
    done = engine.run()
    dt = time.time() - t0
    toks = sum(len(c.tokens) for c in done)
    print(f"arch={args.arch} engine={args.engine} chunk={args.chunk} "
          f"requests={len(done)} tokens={toks} "
          f"wall={dt:.2f}s throughput={toks/dt:,.1f} tok/s "
          f"d2h_transfers={engine.d2h_transfers}")
    if args.engine == "paged":
        print(f"  pages={engine.pool.capacity} page_size={args.page_size} "
              f"prefix_hit_rate={engine.pool.hit_rate:.3f} "
              f"({engine.pool.prefix_hits}/{engine.pool.prefix_lookups})")
    if args.spec_k > 0:
        stats = engine.kv_stats()
        print(f"  spec_k={args.spec_k} "
              f"proposer={'draft:' + args.draft if args.draft else 'ngram'} "
              f"accept_rate={stats['spec_accept_rate']:.3f} "
              f"tokens_per_round={stats['spec_tokens_per_round']:.2f}")
    for c in done[:3]:
        print(f"  uid={c.uid} reason={c.finished_reason} tokens={c.tokens[:8]}...")


if __name__ == "__main__":
    main()
