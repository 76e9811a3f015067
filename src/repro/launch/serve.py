"""Serving driver: compiled batched generation under a composable
protection scheme (the paper's §IV/§V applied to model serving;
DESIGN.md §12/§13).

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-14b --smoke \
      --batch 4 --prompt-len 64 --gen 32 --scheme tmr-parallel \
      --inject-p-bit 1e-4 --vote-every 8

Generation runs through `launch.engine.GenerationEngine`: prefill +
``lax.scan`` over decode steps, so the whole ``--gen``-token generation is
one jitted launch (``--engine loop`` keeps the interpreted per-token
reference path for comparison).  ``--scheme`` accepts ``off | ecc |
tmr-serial | tmr-parallel | tmr-semi | ecc+tmr[-<discipline>]``
(repro.reliability.parse_scheme grammar):

* ``ecc``       — protect the weights with the diagonal-parity word code,
                  corrupt, scrub once (fused launch), serve corrected;
* ``tmr-*``     — three independently corrupted copies stacked on a
                  leading copy axis; 'parallel'/'semi' vmap the generation
                  over it, 'serial' sequences it (lax.map), with per-bit
                  voting of the generated token ids — in-scan every
                  ``--vote-every`` steps, and always on the final
                  sequences;
* ``ecc+tmr-*`` — the joint long-term configuration: one fused ECC scrub
                  over all three copies, then TMR voting.

All scrub/vote counters stay on device during the timed region and are
fetched once after timing stops (no host syncs in the hot path).

Observability (DESIGN.md §15): ``--trace out.json`` records launch spans
as Chrome-trace JSON (load in Perfetto / chrome://tracing), ``--metrics
out.jsonl`` appends structured telemetry records, and ``--chunk N`` runs
chunk-compiled generation with per-chunk latency marks, reporting
TTFT/TPOT p50/p95/p99 tails — all without adding a single device->host
sync to the timed region.

Hardware cost projection (DESIGN.md §17): ``--mmpu-cost`` compiles the
serve's scheme + batch geometry into an mMPU event stream and reports
projected crossbar-cycles and switching energy per token alongside the
wall-clock numbers; ``--mmpu-events out.jsonl`` dumps the stream for
offline analysis (CI uploads it next to trace.json); ``--mmpu-device``
picks a DeviceSpec from configs.mmpu_paper.

Server mode (DESIGN.md §16): ``--server`` serves an open-loop Poisson
trace through the continuous-batching scheduler (paged ECC-protected KV
pool, chunk-boundary admission):

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-14b --smoke \
      --server --rate 8 --requests 32 --slots 4 --scheme ecc+tmr \
      --inject-p-bit 1e-4 --trace trace.json

Arrivals are paced in real time and never wait for service; per-request
TTFT (queue wait included) and TPOT flow through LatencyTimeline, and the
report gives p50/p95/p99 tails plus goodput (useful tokens / wall time).
``--gen`` becomes the per-request generation cap, ``--chunk`` the decode
chunk between scheduling points (default 8), ``--prompt-len`` the single
admission bucket.
"""
from __future__ import annotations

import argparse
import time
from typing import Any, Dict, Optional, Sequence

import jax
import numpy as np

from .. import compile_cache
from ..configs import get_config, list_archs
from ..faults import (FaultModel, RetentionDrift, StuckAtFaults,
                      TransientBitFlips)
from ..models import params as P
from ..models import transformer as T
from ..obs import LatencyTimeline, Tracer
from ..reliability import Compose, Tmr, parse_scheme, scheme_choices, \
    scheme_help
from .batching import BatchSpec, ContinuousBatcher, Request, poisson_trace
from .engine import GenerationEngine, fetch_telemetry
from .mesh import make_test_mesh


def _run_server(args, cfg, key, params, scheme, fault, mesh
                ) -> Dict[str, Any]:
    """Continuous-batching server: open-loop Poisson load through the
    chunk-boundary scheduler over the paged ECC-protected KV pool.
    Returns the trace, its results, the fetched telemetry and the batcher
    (which still holds the store)."""
    chunk = args.chunk or 8
    spec = BatchSpec(slots=args.slots, page_tokens=args.page_tokens,
                     chunk=chunk, prompt_buckets=(args.prompt_len,),
                     gen_cap=args.gen)
    tracer = Tracer(enabled=bool(args.trace or args.metrics))
    b = ContinuousBatcher(cfg, scheme, spec, mesh=mesh,
                          scrub_every=args.scrub_every)
    if getattr(args, "adaptive_scrub", False) and b.ecc is not None:
        from ..runtime import AdaptiveScrub
        # prior sized for the POOL the controller actually scrubs
        b.adaptive = AdaptiveScrub.from_prior(
            args.inject_p_bit, b.pool.arena_spec.n_blocks,
            interval0=max(1, args.scrub_every or 32))
    with tracer.trace("prepare", scheme=scheme.name):
        prep = b.prepare(params, key=key,
                         fault=fault if args.inject_p_bit else None,
                         donate=True)
    del params        # donated to the store
    trace = poisson_trace(args.requests, rate_rps=args.rate, spec=spec,
                          vocab=cfg.vocab, seed=args.seed)
    # compile the admit bucket and the tick program before the open-loop
    # clock starts — arrivals never wait for service, so a cold compile
    # would show up as a queue spike rather than honest latency
    warm = [Request(10**6 + i, t.prompt, min(2, t.gen))
            for i, t in enumerate(trace[:spec.slots])]
    t_warm = time.time()
    with tracer.trace("warmup"):
        b.run(warm)
    t_warm = time.time() - t_warm

    t0 = time.time()
    with tracer.trace("serve", requests=args.requests, rate=args.rate,
                      scheme=scheme.name):
        results = b.run(trace, realtime=True)
    dt = time.time() - t0
    with tracer.trace("fetch_telemetry"):
        stats = fetch_telemetry({**prep, **b.telemetry()})

    useful = sum(len(r.tokens) for r in results)
    goodput = useful / dt
    ttft = np.asarray([r.ttft_s for r in results])
    tpot = np.asarray([s for r in results for s in r.tpot_samples])
    mesh_desc = "single" if mesh is None else \
        "x".join(f"{a}={n}" for a, n in b.engine.exec_mesh.shape.items())
    q = lambda a, p: float(np.percentile(a, p)) if a.size else float("nan")
    print(f"[serve] {cfg.name} server scheme={scheme.name} mesh={mesh_desc} "
          f"p_bit={args.inject_p_bit:g}: {args.requests} reqs @ "
          f"{args.rate:g} rps, slots={spec.slots} chunk={chunk}: "
          f"{useful} tokens in {dt:.1f}s (goodput {goodput:.1f} tok/s, "
          f"{b.ticks} ticks, {b.decode_slot_steps} slot-steps)")
    print(f"[serve] ttft p50={q(ttft, 50) * 1e3:.1f}ms "
          f"p95={q(ttft, 95) * 1e3:.1f}ms p99={q(ttft, 99) * 1e3:.1f}ms; "
          f"tpot p50={q(tpot, 50) * 1e3:.2f}ms p95={q(tpot, 95) * 1e3:.2f}ms "
          f"p99={q(tpot, 99) * 1e3:.2f}ms")
    if stats:
        parts = []
        if "ecc_corrected" in stats:
            parts.append(f"ecc corrected={int(stats['ecc_corrected'])} "
                         f"uncorrectable={int(stats['ecc_uncorrectable'])}")
        if "tmr_final_disagreements" in stats:
            parts.append(f"vote disagreements="
                         f"{int(stats['tmr_final_disagreements'])}")
        print(f"[serve] reliability (fetched after timing): "
              f"{'; '.join(parts) or 'n/a'}")
    if args.trace or args.metrics:
        record = {"kind": "server", "arch": cfg.name, "scheme": scheme.name,
                  "mesh": mesh_desc, "p_bit": args.inject_p_bit,
                  "rate_rps": args.rate, "requests": args.requests,
                  "slots": spec.slots, "chunk": chunk, "gen_cap": args.gen,
                  "goodput_tok_s": goodput, "ticks": b.ticks,
                  "decode_slot_steps": b.decode_slot_steps,
                  "ttft_p50_s": q(ttft, 50), "ttft_p95_s": q(ttft, 95),
                  "ttft_p99_s": q(ttft, 99),
                  "tpot_p50_s": q(tpot, 50), "tpot_p95_s": q(tpot, 95),
                  "tpot_p99_s": q(tpot, 99),
                  **{k: (np.asarray(v).sum().item()
                         if hasattr(v, "shape") else v)
                     for k, v in stats.items()}}
        tracer.metrics(record, kind="server")
        if args.trace:
            tracer.write_chrome(args.trace)
            print(f"[serve] chrome trace -> {args.trace} "
                  f"(load in Perfetto / chrome://tracing)")
        if args.metrics:
            tracer.write_jsonl(args.metrics)
            print(f"[serve] metrics jsonl -> {args.metrics}")
    return {"trace": trace, "results": results, "stats": stats,
            "batcher": b, "warmup_s": t_warm, "serve_s": dt}


def main(argv: Optional[Sequence[str]] = None) -> Dict[str, Any]:
    """Serve as the command line says; returns what was served (server
    mode: `_run_server`'s dict; otherwise the tokens, the clean-run
    reference when faults were injected, the fetched telemetry, and the
    engine with its store and batch, which a caller drops to free the
    store)."""
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="qwen2.5-14b", choices=list_archs())
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--scheme", default="off",
                    metavar="|".join(scheme_choices()),
                    help="protection scheme spec, from the scheme registry"
                         " (reliability.register_scheme) — "
                         + scheme_help())
    ap.add_argument("--engine", default="scan", choices=["scan", "loop"],
                    help="scan: one compiled prefill+scan launch (default);"
                         " loop: interpreted per-token reference path")
    ap.add_argument("--mesh", default=None, metavar="DATAxMODEL",
                    help="shard the engine over a DATAxMODEL device mesh "
                         "(e.g. 2x2; DESIGN.md §14).  TMR copy axes fold "
                         "onto data replica groups when data %% 3 == 0.  "
                         "On CPU force devices first: XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N")
    ap.add_argument("--vote-every", type=int, default=0,
                    help="TMR/Compose: vote token ids across copies every k "
                         "decode steps inside the scan (0 = only at the end)")
    ap.add_argument("--vote-cache", action="store_true",
                    help="also vote the KV caches at in-scan vote points")
    ap.add_argument("--inject-p-bit", type=float, default=0.0,
                    help="corrupt each weight bit of each copy w.p. p")
    ap.add_argument("--fault", default="bitflip",
                    choices=["bitflip", "stuckat", "drift"],
                    help="fault model driving the per-copy corruption "
                         "(repro.faults taxonomy; rate = --inject-p-bit)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write launch spans as Chrome-trace JSON "
                         "(Perfetto / chrome://tracing loadable)")
    ap.add_argument("--metrics", default=None, metavar="PATH",
                    help="append structured telemetry records as JSONL")
    ap.add_argument("--chunk", type=int, default=0,
                    help="generate in compiled N-token chunk launches with "
                         "per-chunk latency marks: reports TTFT/TPOT "
                         "p50/p95/p99 tails (0 = one scan launch, no "
                         "tails; bit-exact either way)")
    ap.add_argument("--server", action="store_true",
                    help="continuous-batching server mode: serve an "
                         "open-loop Poisson trace through the "
                         "chunk-boundary scheduler over the paged "
                         "ECC-protected KV pool (DESIGN.md §16); --gen is "
                         "the per-request cap, --chunk the decode chunk "
                         "(default 8), --prompt-len the admission bucket")
    ap.add_argument("--rate", type=float, default=8.0,
                    help="server mode: Poisson arrival rate, requests/s")
    ap.add_argument("--requests", type=int, default=32,
                    help="server mode: number of requests in the trace")
    ap.add_argument("--scrub-every", type=int, default=0, metavar="TICKS",
                    help="server: fixed pool-scrub cadence in scheduler "
                         "ticks (0 = no periodic scrub)")
    ap.add_argument("--adaptive-scrub", action="store_true",
                    help="server: pay-as-you-fault scrub cadence — the "
                         "runtime.AdaptiveScrub controller moves the "
                         "interval from observed correction rates "
                         "(--scrub-every seeds interval0; overrides the "
                         "fixed cadence)")
    ap.add_argument("--slots", type=int, default=4,
                    help="server mode: fixed batch slots (bounds the "
                         "compile cache; empty slots are masked)")
    ap.add_argument("--mmpu-cost", action="store_true",
                    help="project this serve onto the mMPU cost model "
                         "(costmodel/, DESIGN.md §17): report cycles/token "
                         "and energy/token for the chosen scheme and stamp "
                         "mmpu_* gauges into the telemetry")
    ap.add_argument("--mmpu-events", default=None, metavar="PATH",
                    help="dump the compiled MmpuEvent stream as JSONL "
                         "(implies --mmpu-cost)")
    ap.add_argument("--mmpu-device", default="paper",
                    help="DeviceSpec name from configs.mmpu_paper "
                         "(default: paper)")
    ap.add_argument("--page-tokens", type=int, default=16,
                    help="server mode: tokens per KV pool page")
    args = ap.parse_args(argv)

    if args.engine == "loop" and (args.vote_every or args.vote_cache):
        ap.error("--vote-every/--vote-cache only apply to the scan engine "
                 "(the loop reference votes final sequences only); drop "
                 "the flags or use --engine scan")
    scheme = parse_scheme(args.scheme)
    if args.vote_every or args.vote_cache:
        tmr = scheme if isinstance(scheme, Tmr) \
            else scheme.tmr if isinstance(scheme, Compose) else None
        if tmr is None:
            ap.error(f"--vote-every/--vote-cache need a copy axis to vote "
                     f"over; scheme {scheme.name!r} has none (use --scheme "
                     f"tmr-* or ecc+tmr[-*])")
        if tmr.discipline == "serial":
            ap.error("in-scan voting needs concurrently executing copies; "
                     "the serial discipline re-runs them sequentially (use "
                     "tmr-parallel/tmr-semi, or drop the vote flags)")
    if args.vote_cache and not args.vote_every:
        ap.error("--vote-cache needs --vote-every K (cache votes happen at "
                 "the in-scan vote points)")
    if args.chunk and args.engine == "loop":
        ap.error("--chunk requires the scan engine (the loop reference is "
                 "already per-token)")
    if args.chunk < 0:
        ap.error(f"--chunk must be >= 0, got {args.chunk}")
    if args.server:
        if args.engine == "loop":
            ap.error("--server runs the compiled scheduler; --engine loop "
                     "does not apply")
        if args.vote_every or args.vote_cache:
            ap.error("--server votes each finished request's tokens from "
                     "the completion fetch; in-scan vote flags do not "
                     "apply")
        if args.rate <= 0 or args.requests < 1 or args.slots < 1:
            ap.error("--server needs --rate > 0, --requests >= 1 and "
                     "--slots >= 1")

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    key = jax.random.PRNGKey(args.seed)
    # served weights live in the compute dtype every matmul casts to
    params = P.materialize(key, T.model_specs(cfg), dtype=cfg.cdtype)

    batch = {"tokens": jax.random.randint(key, (args.batch, args.prompt_len),
                                          0, cfg.vocab)}
    if cfg.family == "vlm":
        batch["vis_emb"] = jax.random.normal(
            key, (args.batch, cfg.vis_tokens, cfg.vis_dim), np.float32)
    if cfg.family == "encdec":
        batch["enc_emb"] = jax.random.normal(
            key, (args.batch, args.prompt_len, cfg.d_model), np.float32)

    fault: FaultModel = {
        "bitflip": TransientBitFlips(args.inject_p_bit),
        "stuckat": StuckAtFaults(args.inject_p_bit / 2,
                                 args.inject_p_bit / 2),
        "drift": RetentionDrift(args.inject_p_bit),
    }[args.fault]

    mesh = None
    if args.mesh:
        try:
            data, model = (int(t) for t in args.mesh.lower().split("x"))
        except ValueError:
            ap.error(f"--mesh expects DATAxMODEL (e.g. 2x2), got "
                     f"{args.mesh!r}")
        mesh = make_test_mesh(data, model)

    if args.server:
        return _run_server(args, cfg, key, params, scheme, fault, mesh)

    tracer = Tracer(enabled=bool(args.trace or args.metrics))
    cost_spec = None
    if args.mmpu_cost or args.mmpu_events:
        from ..configs.mmpu_paper import get_device
        cost_spec = get_device(args.mmpu_device)
    engine = GenerationEngine(cfg, scheme, gen=args.gen,
                              vote_every=args.vote_every,
                              vote_cache=args.vote_cache,
                              execution=args.engine, mesh=mesh,
                              cost_spec=cost_spec)
    ref = None
    if args.inject_p_bit:
        # the clean reference runs first: prepare takes the weights' memory
        # for the store, so no clean copy is kept beside it.  off/ecc
        # stores are plain params pytrees, so the engine's own compiled
        # single-copy program serves it; copy-axis schemes need a
        # single-copy engine
        clean = GenerationEngine(cfg, gen=args.gen, execution=args.engine) \
            if engine.copy_axis else engine
        ref = np.asarray(clean.generate(params, batch)[0])
    with tracer.trace("prepare", scheme=scheme.name):
        store, prep = engine.prepare(
            params, key=key, fault=fault if args.inject_p_bit else None,
            donate=True)
    del params        # donated to the store
    # keep compile and prepare's async corrupt/scrub launches out of the
    # timed region: one untimed warmup generation, then drain the store
    t_warm = time.time()
    with tracer.trace("warmup"):
        if args.chunk:
            jax.block_until_ready(
                engine.generate_chunked(store, batch, chunk=args.chunk)[0])
        else:
            jax.block_until_ready(engine.generate(store, batch)[0])
        store = jax.block_until_ready(store)
    t_warm = time.time() - t_warm

    # timed region: no host syncs — telemetry stays on device until after
    timeline = None
    t0 = time.time()
    with tracer.trace("generate", scheme=scheme.name, gen=args.gen,
                      chunk=args.chunk):
        if args.chunk:
            out, telem, timeline = engine.generate_chunked(
                store, batch, chunk=args.chunk, tracer=tracer)
        else:
            out, telem = engine.generate(store, batch)
        out = jax.block_until_ready(out)
    dt = time.time() - t0

    with tracer.trace("fetch_telemetry"):
        stats = fetch_telemetry({**prep, **telem})   # the single fetch
    out = np.asarray(out)
    agree = float((out == ref).mean()) if ref is not None else 1.0
    tok_s = args.batch * args.gen / dt
    mesh_desc = "single" if mesh is None else \
        "x".join(f"{a}={n}" for a, n in engine.exec_mesh.shape.items())
    print(f"[serve] {cfg.name} scheme={scheme.name} engine={args.engine} "
          f"mesh={mesh_desc} "
          f"p_bit={args.inject_p_bit:g}: {args.batch}x{args.gen} tokens "
          f"in {dt:.1f}s ({tok_s:.1f} tok/s), "
          f"agreement with clean run: {agree:.3f}")
    if stats:
        parts = []
        if "ecc_corrected" in stats:
            parts.append(f"ecc corrected={int(stats['ecc_corrected'])} "
                         f"uncorrectable={int(stats['ecc_uncorrectable'])}")
        if "tmr_final_disagreements" in stats:
            parts.append("vote disagreements: final="
                         f"{int(stats['tmr_final_disagreements'])}")
        if "tmr_step_disagreements" in stats:
            steps = np.asarray(stats["tmr_step_disagreements"])
            parts.append(f"per-step={steps.sum()} over {steps.size} steps")
        print(f"[serve] reliability (fetched after timing): "
              f"{'; '.join(parts)}")
    print(f"[serve] cost model ({scheme.name}): {scheme.overhead().describe()}")
    if cost_spec is not None:
        stream, cost = engine.mmpu_projection(args.batch)
        print(f"[serve] mMPU projection ({cost_spec.name}): "
              f"{cost.describe()}")
        if args.mmpu_events:
            from ..costmodel import dump_jsonl
            n = dump_jsonl(stream, args.mmpu_events)
            print(f"[serve] mmpu event stream -> {args.mmpu_events} "
                  f"({n} events)")
    if timeline is not None:
        lat = timeline.summary()
        print(f"[serve] latency tails (chunk={args.chunk}): "
              f"ttft={lat['ttft_s'] * 1e3:.1f}ms "
              f"tpot p50={lat.get('tpot_p50', float('nan')) * 1e3:.2f}ms "
              f"p95={lat.get('tpot_p95', float('nan')) * 1e3:.2f}ms "
              f"p99={lat.get('tpot_p99', float('nan')) * 1e3:.2f}ms")
    if args.trace or args.metrics:
        record = {"kind": "serve", "arch": cfg.name, "scheme": scheme.name,
                  "engine": args.engine, "mesh": mesh_desc,
                  "p_bit": args.inject_p_bit, "batch": args.batch,
                  "gen": args.gen, "chunk": args.chunk, "tok_s": tok_s,
                  "agreement": agree,
                  **{k: (np.asarray(v).sum().item()
                         if hasattr(v, "shape") else v)
                     for k, v in stats.items()}}
        if timeline is not None:
            record.update({k: float(v)
                           for k, v in timeline.summary().items()})
        tracer.metrics(record, kind="serve")
        if args.trace:
            tracer.write_chrome(args.trace)
            print(f"[serve] chrome trace -> {args.trace} "
                  f"(load in Perfetto / chrome://tracing)")
        if args.metrics:
            tracer.write_jsonl(args.metrics)
            print(f"[serve] metrics jsonl -> {args.metrics}")
    print("[serve] sample:", out[0, :16].tolist())
    return {"tokens": out, "reference": ref, "stats": stats,
            "warmup_s": t_warm, "generate_s": dt,
            "engine": engine, "store": store, "batch": batch}


if __name__ == "__main__":
    compile_cache.enable()
    main()
