"""Chip smoke test: protected phi3-mini-3.8b serving at published widths.

Drives the serving path once, through the command-line entry point
`repro.launch.serve` (`main(argv)`), on random weights made from `--seed`:

    python chip_smoke.py              # one TPU chip
    python chip_smoke.py --four-chip  # one four-chip TPU host
    python chip_smoke.py --smoke      # rehearsal at .smoke() widths

One chip, three phases, all at 32 layers, d_model 3072:

  off       the server path (`serve --server`: ContinuousBatcher over the
            paged KV pool) on a small Poisson trace in bfloat16; two of its
            requests are then served again alone and must give the tokens
            they gave in the batch (row locality, DESIGN.md §16).
  hsiao-wb  the same trace with the weights corrupted and Hsiao-scrubbed,
            write-back KV repair and the adaptive pool scrub on; every
            fault must be corrected and every token must equal `off`'s.
  ecc       diagonal parity through the compiled GenerationEngine (scan
            engine, chunked); its tokens must equal an `off` engine run on
            the same batch.

`--four-chip` runs only `off` on a single chip and `ecc+tmr-parallel` over
`--mesh 3x1`, one copy per chip, and compares the voted tokens; then it
corrupts copy 1 alone on its chip, after the scrub, and generates again:
the copies must disagree and the vote must still give `off`'s tokens.

Fault rates come from the closed forms in `core.analytics`: the largest
rate on the Bernoulli sampler's 2^-23 grid whose expected count of
uncorrectable words (Hsiao) or blocks (diagonal parity) stays below
1e-3.  Where even the grid's floor exceeds that, `ecc` and `ecc+tmr` run
without faults and `hsiao-wb`, which needs faults to correct, runs at the
floor.

Every check raises; nothing is caught.  The last line of standard output
is one JSON object, printed only after every check passed on a TPU:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Without a TPU the script exits non-zero before building anything (with
`--smoke`, after the rehearsal).
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "phi3-mini-3.8b"
# the Bernoulli sampler compares a float32 uniform drawn on a 2^-23 grid,
# so every rate below 2^-23 flips bits at 2^-23
P_FLOOR = 2.0 ** -23
UNCORRECTABLE_TARGET = 1e-3
SERVER = ["--server", "--requests", "8", "--slots", "4", "--rate", "8",
          "--prompt-len", "128", "--gen", "32", "--chunk", "8",
          "--page-tokens", "16"]
ENGINE = ["--batch", "4", "--prompt-len", "128", "--gen", "32",
          "--chunk", "8"]
KERNEL_OPS = ("diag_parity", "hsiao_secded", "inject_scrub", "tmr_vote")


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def memory() -> dict:
    import jax
    out = {}
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        out[d.id] = {k: stats.get(k) for k in ("bytes_in_use",
                                               "peak_bytes_in_use",
                                               "bytes_limit")}
    return out


def report(phase: str, t0: float, result: dict) -> None:
    timing = {k: round(v, 3) for k, v in result.items() if k.endswith("_s")}
    print(f"[chip_smoke] {phase}: wall {time.perf_counter() - t0:.1f}s "
          f"{timing}")
    for dev, m in memory().items():
        print(f"[chip_smoke] {phase}: device {dev} {m}")


def pick_p_bit(n_units: int, words_per_unit: int):
    """Largest rate on the sampler's grid whose expected uncorrectable
    units (words for Hsiao, 32-word blocks for diagonal parity) per scrub
    stay below the target; None if even the floor exceeds it."""
    from repro.core.analytics import expected_scrub_rates

    def bad(k: int) -> float:
        return expected_scrub_rates(k * P_FLOOR, n_units,
                                    words_per_block=words_per_unit
                                    )["uncorrectable_per_scrub"]

    if bad(1) > UNCORRECTABLE_TARGET:
        return None
    lo, hi = 1, 2
    while bad(hi) <= UNCORRECTABLE_TARGET:
        lo, hi = hi, hi * 2
    while hi - lo > 1:
        mid = (lo + hi) // 2
        lo, hi = (mid, hi) if bad(mid) <= UNCORRECTABLE_TARGET else (lo, mid)
    return lo * P_FLOOR


def expectation(p: float, n_units: int, words_per_unit: int) -> str:
    from repro.core.analytics import expected_scrub_rates
    e = expected_scrub_rates(p, n_units, words_per_block=words_per_unit)
    return (f"p_bit={p!r}: expected corrected {e['corrected_per_scrub']:.6g}"
            f", uncorrectable {e['uncorrectable_per_scrub']:.6g}")


def backend_check(on_tpu: bool) -> None:
    """Every op's resolved implementation; the arena kernels must resolve
    to `kernel` and compile to Mosaic custom calls, not the interpreter."""
    import jax
    import jax.numpy as jnp
    from repro.reliability import backend

    impls = {op: backend.resolve(op) for op in backend.ops()}
    print(f"[chip_smoke] resolved implementations: {impls}")
    print(f"[chip_smoke] pallas interpret mode: {backend.use_interpret()}")
    for op in KERNEL_OPS:
        check(impls[op] == "kernel", f"{op} resolves to {impls[op]}")
    buf = jnp.arange(512 * 32, dtype=jnp.uint32)
    diag, hsiao = backend.dispatch("diag_parity"), \
        backend.dispatch("hsiao_secded")
    programs = {
        "diag_parity": lambda b: diag.scrub(b, diag.encode(b)),
        "hsiao_secded": lambda b: hsiao.scrub(b, hsiao.encode(b)),
        "inject_scrub": lambda b: backend.dispatch("inject_scrub")(
            b, diag.encode(b), b & 1),
        "tmr_vote": lambda b: backend.dispatch("tmr_vote")(b, b, b ^ 1),
    }
    for op, fn in programs.items():
        text = jax.jit(fn).lower(buf).compile().as_text()
        mosaic = "tpu_custom_call" in text
        print(f"[chip_smoke] {op}: compiled Mosaic kernel: {mosaic}")
        if on_tpu:
            check(not backend.use_interpret() and mosaic,
                  f"{op} does not run as a compiled kernel")


def weight_words(cfg) -> int:
    """uint32 words of the model's weights in the compute dtype."""
    import jax
    from repro.core import arena
    from repro.models.params import Spec
    from repro.models.transformer import model_specs
    specs = jax.tree.leaves(model_specs(cfg),
                            is_leaf=lambda x: isinstance(x, Spec))
    n_words = sum(arena.words_for(s.shape, cfg.cdtype) for s in specs)
    print(f"[chip_smoke] {cfg.name}: {cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, {cfg.n_heads} heads, d_ff {cfg.d_ff}, vocab "
          f"{cfg.vocab}; {n_words} weight words in {cfg.compute_dtype}")
    return n_words


def diag_faults(n_words: int, phase: str) -> list:
    """`--inject-p-bit` for a diagonal-parity phase: the analytics rate,
    or no faults where even the sampler's floor leaves uncorrectable
    blocks likely (an uncorrected bfloat16 exponent flip derails a copy's
    generation)."""
    from repro.core import arena
    n_blocks = n_words // arena.BLOCK
    p = pick_p_bit(n_blocks, arena.BLOCK)
    print(f"[chip_smoke] {phase}: " + (
        expectation(p, n_blocks, arena.BLOCK) + " blocks" if p is not None
        else f"no faults: even the floor gives "
             f"{expectation(P_FLOOR, n_blocks, arena.BLOCK)} blocks"))
    return [] if p is None else ["--inject-p-bit", repr(p)]


def one_chip(serve, common, cfg) -> None:
    import numpy as np
    from repro.launch.batching import Request

    n_words = weight_words(cfg)
    t0 = time.perf_counter()
    off = serve(common + SERVER + ["--scheme", "off"])
    tokens = {r.rid: r.tokens for r in off["results"]}
    for req in off["trace"][:2]:
        alone = off["batcher"].run(
            [Request(10**6 + req.rid, req.prompt, req.gen)])[0]
        check(np.array_equal(alone.tokens, tokens[req.rid]),
              f"request {req.rid}: alone {alone.tokens} != in batch "
              f"{tokens[req.rid]}")
    print(f"[chip_smoke] off: {len(tokens)} requests served; requests "
          f"{[r.rid for r in off['trace'][:2]]} alone == in batch")
    report("off", t0, off)
    del off
    gc.collect()

    t0 = time.perf_counter()
    p = pick_p_bit(n_words, 1) or P_FLOOR
    print(f"[chip_smoke] hsiao-wb: {expectation(p, n_words, 1)} words")
    hs = serve(common + SERVER + ["--scheme", "hsiao-wb", "--adaptive-scrub",
                                  "--inject-p-bit", repr(p)])
    stats = hs["stats"]
    print(f"[chip_smoke] hsiao-wb: telemetry {stats}")
    check(int(stats["ecc_corrected"]) > 0, "hsiao-wb corrected nothing")
    check(int(stats["ecc_uncorrectable"]) == 0,
          "hsiao-wb left uncorrectable words")
    for r in hs["results"]:
        check(np.array_equal(r.tokens, tokens[r.rid]),
              f"hsiao-wb request {r.rid}: {r.tokens} != off {tokens[r.rid]}")
    print(f"[chip_smoke] hsiao-wb: all {len(hs['results'])} requests == off")
    report("hsiao-wb", t0, hs)
    del hs
    gc.collect()

    t0 = time.perf_counter()
    inject = diag_faults(n_words, "ecc")
    ref = serve(common + ENGINE + ["--scheme", "off"])
    report("ecc (off engine run)", t0, ref)
    ref = ref["tokens"]                 # drops the off store
    ecc = serve(common + ENGINE + ["--scheme", "ecc"] + inject)
    print(f"[chip_smoke] ecc: telemetry {ecc['stats']}")
    check(np.array_equal(ecc["tokens"], ref),
          "ecc engine tokens != off engine tokens")
    if inject:
        check(int(ecc["stats"]["ecc_uncorrectable"]) == 0,
              "ecc left uncorrectable blocks")
    print(f"[chip_smoke] ecc: {ecc['tokens'].shape} tokens == off")
    report("ecc", t0, ecc)


def corrupt_copy(store, copy: int, p: float, seed: int):
    """A copy-sharded TMR store with copy `copy` corrupted at `p_bit` on
    its own chip, leaf by leaf, the other copies' buffers untouched.
    Consumes `store`: each old leaf is dropped as its new one is made."""
    import jax
    from repro.faults import TransientBitFlips
    from repro.launch.engine import donate_argnums
    fault = TransientBitFlips(p)
    corrupt = jax.jit(lambda a, k: fault.corrupt(a[0], k)[None],
                      donate_argnums=donate_argnums(0))
    leaves, treedef = jax.tree.flatten(store)
    del store
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    for j, k in enumerate(keys):
        x, leaves[j] = leaves[j], None
        shards = [corrupt(s.data, k) if (s.index[0].start or 0) == copy
                  else s.data for s in x.addressable_shards]
        leaves[j] = jax.make_array_from_single_device_arrays(
            x.shape, x.sharding, shards)
        del x, shards
    return treedef.unflatten(leaves)


def four_chip(serve, common, cfg, seed: int) -> None:
    import numpy as np
    from repro.launch.engine import fetch_telemetry
    n_words = weight_words(cfg)
    inject = diag_faults(n_words, "ecc+tmr")
    t0 = time.perf_counter()
    ref = serve(common + ENGINE + ["--scheme", "off"])
    report("off (single chip)", t0, ref)
    ref = ref["tokens"]                 # drops the off store
    t0 = time.perf_counter()
    tmr = serve(common + ENGINE + ["--scheme", "ecc+tmr-parallel",
                                   "--mesh", "3x1"] + inject)
    print(f"[chip_smoke] ecc+tmr: telemetry {tmr['stats']}")
    print(f"[chip_smoke] ecc+tmr: {np.mean(tmr['tokens'] == ref)}"
          f" of tokens equal single-chip off; first row {tmr['tokens'][0]}"
          f" vs {ref[0]}")
    check(np.array_equal(tmr["tokens"], ref),
          "voted ecc+tmr tokens != single-chip off tokens")
    print(f"[chip_smoke] ecc+tmr: voted {tmr['tokens'].shape} tokens == "
          f"single-chip off")
    report("ecc+tmr (mesh 3x1)", t0, tmr)

    # faults in one copy after its scrub: about 1e4 flips, enough to
    # derail that copy's generation, on the sampler's grid
    t0 = time.perf_counter()
    p = max(P_FLOOR, 2.0 ** round(np.log2(1e4 / (32 * n_words))))
    engine, batch = tmr["engine"], tmr["batch"]
    store = corrupt_copy(tmr.pop("store"), 1, p, seed + 1)
    tokens, telem = engine.generate(store, batch)
    stats = fetch_telemetry(telem)
    tokens = np.asarray(tokens)
    dis = int(stats["tmr_final_disagreements"])
    print(f"[chip_smoke] ecc+tmr, copy 1 corrupted at p_bit={p!r}: "
          f"{dis} token positions where the copies disagree")
    check(dis > 0, "corrupting copy 1 changed none of its tokens")
    check(np.array_equal(tokens, ref),
          "voted tokens with copy 1 corrupted != single-chip off tokens")
    print(f"[chip_smoke] ecc+tmr, copy 1 corrupted: voted {tokens.shape} "
          f"tokens == single-chip off")
    report("ecc+tmr, copy 1 corrupted", t0, {})


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="ecc+tmr-parallel over --mesh 3x1 vs single-chip "
                         "off, on a four-chip host")
    ap.add_argument("--smoke", action="store_true",
                    help="rehearse at .smoke() widths on any backend "
                         "(still exits non-zero without a TPU)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro import compile_cache
    cache = compile_cache.enable()      # before the backend starts
    import jax
    devices = jax.devices()
    on_tpu = devices[0].platform == "tpu"
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    print(f"[chip_smoke] devices: {device}")
    if not on_tpu and not args.smoke:
        print("[chip_smoke] no TPU: nothing was run", file=sys.stderr)
        return 2
    if args.four_chip and len(devices) < 4:
        print(f"[chip_smoke] --four-chip needs 4 devices, found "
              f"{len(devices)}", file=sys.stderr)
        return 2

    from repro.configs import get_config
    from repro.launch import serve
    print(f"[chip_smoke] compilation cache: {cache}")
    backend_check(on_tpu)

    cfg = get_config(ARCH)
    common = ["--arch", ARCH, "--seed", str(args.seed)]
    if args.smoke:
        cfg = cfg.smoke()
        common.append("--smoke")
    t0 = time.perf_counter()
    if args.four_chip:
        four_chip(serve.main, common, cfg, args.seed)
    else:
        one_chip(serve.main, common, cfg)
    print(f"[chip_smoke] all phases passed in "
          f"{time.perf_counter() - t0:.1f}s")
    if not on_tpu:
        print("[chip_smoke] rehearsal only: no TPU", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
