"""Serving launcher: loads (or initializes) a model, spins up the
continuous-batching engine, runs a batch of synthetic requests and reports
throughput/latency stats. On a TPU, prefill attention runs on the Pallas
flash kernel (SSD layers on the SSD kernel).

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --reduced \
        --requests 8 --slots 4
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np

from repro.compile_cache import configure_compile_cache
from repro.configs import get_config, reduced_config
from repro.models import model as M
from repro.models.runtime import CPU_TEST, Runtime
from repro.serve.engine import Request, ServeEngine
from repro.train import checkpoint as ckpt


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--ckpt-dir", default=None,
                    help="restore params from the latest checkpoint here")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args(argv)
    configure_compile_cache()

    cfg = reduced_config(args.arch) if args.reduced else get_config(args.arch)
    if cfg.family in ("encdec", "vlm"):
        raise SystemExit("engine serves decoder-only families; use "
                         "serve_step.make_prefill_step/make_decode_step "
                         "directly for encdec/vlm")
    rt = dataclasses.replace(CPU_TEST if args.reduced else Runtime(),
                             use_pallas=jax.default_backend() == "tpu")
    if args.ckpt_dir:
        restored = ckpt.restore_latest(args.ckpt_dir)
        if restored is None:
            raise SystemExit(f"no checkpoint under {args.ckpt_dir}")
        params_np, _, meta = restored
        params = ckpt.to_device(params_np)
        print(f"[serve] restored step {meta['step']} from {args.ckpt_dir}")
    else:
        params = M.init_params(jax.random.PRNGKey(0), cfg)
        print("[serve] random-init params (pass --ckpt-dir for trained)")

    engine = ServeEngine(cfg, rt, params, slots=args.slots,
                         max_len=args.max_len)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab, size=4 + (i % 5) * 3),
                    max_new_tokens=args.max_new,
                    temperature=args.temperature)
            for i in range(args.requests)]
    t0 = time.time()
    outs = engine.run(reqs)
    dt = time.time() - t0
    total = sum(len(v) for v in outs.values())
    print(f"[serve] {len(reqs)} requests -> {total} tokens in {dt:.2f}s "
          f"({total / dt:.1f} tok/s, {args.slots} slots)")
    for rid in sorted(outs)[:4]:
        print(f"  req {rid}: {outs[rid][:10]}{'...' if len(outs[rid]) > 10 else ''}")
    return outs


if __name__ == "__main__":
    main()
