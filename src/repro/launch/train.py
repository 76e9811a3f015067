"""Training driver: composes configs, data, optimizer, checkpointing,
fault-tolerance monitoring and the reliability layer into a runnable loop.

On this CPU container it runs reduced (smoke) configs end-to-end; on a real
cluster the same driver runs the full config against the production mesh
(--mesh data,model sizes).  Examples:

  PYTHONPATH=src python -m repro.launch.train --arch mamba2-130m --smoke \
      --steps 50 --batch 8 --seq 256 --ecc-scrub-every 10
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from .. import compile_cache
from ..checkpoint import Checkpointer
from ..configs import get_config, get_train_policy, list_archs
from ..data.synthetic import SyntheticLM
from ..models import params as P
from ..models import transformer as T
from ..models.steps import init_train_state, make_train_step
from ..obs import NULL_TRACER, Tracer
from ..optim import AdamWConfig
from ..pshard import DEFAULT_RULES, use_mesh_and_rules
from ..reliability import SCHEME_CHOICES, Unprotected, parse_scheme
from ..runtime import LoopConfig, TrainLoop


def build(args, tracer: Tracer = NULL_TRACER):
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    cfg = cfg.replace(compute_dtype=args.compute_dtype)

    key = jax.random.PRNGKey(args.seed)
    specs = T.model_specs(cfg)
    params = P.materialize(key, specs, jnp.dtype(args.param_dtype))
    n_params = sum(x.size for x in jax.tree.leaves(params))

    opt_cfg = AdamWConfig(lr=args.lr, total_steps=args.steps,
                          warmup_steps=max(args.steps // 20, 5))
    train_step = jax.jit(make_train_step(
        cfg, opt_cfg, grad_compression=args.grad_compression,
        microbatches=args.microbatches))
    state = init_train_state(params, grad_compression=args.grad_compression)

    data = SyntheticLM(vocab=cfg.vocab, seq_len=args.seq,
                       batch_per_rank=args.batch, seed=args.seed)

    def batch_at(step):
        b = {"tokens": jnp.asarray(data.batch_at(step))}
        if cfg.family == "vlm":
            b["vis_emb"] = jax.random.normal(
                jax.random.fold_in(key, step), (args.batch, cfg.vis_tokens, cfg.vis_dim),
                jnp.float32)
        if cfg.family == "encdec":
            b["enc_emb"] = jax.random.normal(
                jax.random.fold_in(key, step), (args.batch, args.seq, cfg.d_model),
                jnp.float32)
        return b

    ckpt = Checkpointer(args.ckpt_dir, keep=2) if args.ckpt_dir else None
    loop_cfg = LoopConfig(total_steps=args.steps,
                          checkpoint_every=args.checkpoint_every,
                          scrub_every=args.ecc_scrub_every,
                          log_every=args.log_every,
                          inject_p_bit=args.inject_p_bit,
                          scheme=parse_scheme(args.scheme))
    loop = TrainLoop(train_step, state, batch_at, loop_cfg, ckpt=ckpt,
                     tracer=tracer)
    if args.ecc_scrub_every and not isinstance(loop_cfg.scheme, Unprotected):
        loop.attach_scheme()
    return cfg, loop, n_params


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="mamba2-130m", choices=list_archs())
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--param-dtype", default="float32")
    ap.add_argument("--compute-dtype", default="float32")
    ap.add_argument("--grad-compression", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--ecc-scrub-every", type=int, default=0)
    ap.add_argument("--scheme", default="ecc",
                    help="protection scheme armed when --ecc-scrub-every > 0 "
                         "(repro.reliability.parse_scheme grammar, e.g. "
                         + " | ".join(SCHEME_CHOICES)
                         + " | ecc+tmr-semi; DESIGN.md §12)")
    ap.add_argument("--inject-p-bit", type=float, default=0.0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write loop spans (train_step/scrub/checkpoint/"
                         "eval) as Chrome-trace JSON (DESIGN.md §15)")
    ap.add_argument("--metrics", default=None, metavar="PATH",
                    help="append heartbeat/scrub records as JSONL")
    args = ap.parse_args()

    tracer = Tracer(enabled=bool(args.trace or args.metrics))
    cfg, loop, n_params = build(args, tracer=tracer)
    print(f"[train] {cfg.name} ({cfg.family}) params={n_params/1e6:.1f}M "
          f"steps={args.steps} batch={args.batch}x{args.seq}")
    if args.resume:
        loop.restore()
    t0 = time.time()
    summary = loop.run()
    dt = time.time() - t0
    tok_s = args.steps * args.batch * args.seq / dt
    print(f"[train] done: {summary} | {dt:.1f}s, {tok_s:,.0f} tok/s")
    if loop.scrub_reports:
        tot = sum(int(r.corrected) for _, r in loop.scrub_reports)
        print(f"[reliability] scrubs={len(loop.scrub_reports)} corrected_bits={tot}")
    if args.trace:
        tracer.write_chrome(args.trace)
        print(f"[train] chrome trace -> {args.trace} "
              f"(load in Perfetto / chrome://tracing)")
    if args.metrics:
        tracer.metrics({"final_step": summary["final_step"],
                        "tok_s": tok_s, **summary["monitor"]},
                       kind="train_summary")
        tracer.write_jsonl(args.metrics)
        print(f"[train] metrics jsonl -> {args.metrics}")


if __name__ == "__main__":
    compile_cache.enable()
    main()
