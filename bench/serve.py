"""Drives the serving program under test through one cell.

The system under test is `repro.launch.batching.ContinuousBatcher`, used
through its public surface only: `prepare`, `submit`, `admit`, `tick`,
`drain`, `results`.  This module builds it from a configuration file and
a traffic mix, warms every program the mix will run, and then drives the
window from the benchmark's own loop, marking each admission and tick on
the host clock (and, in a traced run, as profiler annotations).

The window opens at a tick boundary.  Each turn of the loop that starts
before the deadline sends what is due, admits what fits and runs one
tick; the window closes at the end of that turn's tick.  After it, no
request falls due (an open loop still sends, late, those due before the
close) and the batcher is drained outside the window.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import traffic as T
from . import weights as W
from .faults import SparseBitFlips

#: requests per closed-loop client: more than any window can use
PER_CLIENT = 1024
#: the warm-up's own request ids, apart from the window's
WARM_RID = 10 ** 9


@dataclasses.dataclass
class Sent:
    rid: int
    prompt: np.ndarray
    gen: int
    due: float                      # host clock, seconds
    sent: float = float("nan")
    admit_launch: float = float("nan")
    ticks: int = 0                  # ticks run while it held a slot
    marks: List[tuple] = dataclasses.field(default_factory=list)
    tokens: Optional[np.ndarray] = None

    @property
    def first(self) -> float:
        return self.marks[0][0] if self.marks else float("nan")

    @property
    def last(self) -> float:
        return self.marks[-1][0] if self.marks else float("nan")


@dataclasses.dataclass
class Window:
    t0: float
    t1: float = float("nan")
    requests: List[Sent] = dataclasses.field(default_factory=list)
    failed: int = 0
    ticks: int = 0
    #: per tick in the window: the tokens each active slot holds in its
    #: cache, summed over slots, at the middle of the tick's chunk
    live_kv_tokens: List[float] = dataclasses.field(default_factory=list)
    #: requests waiting after each turn, for the knee sweep
    queue_after_turn: List[int] = dataclasses.field(default_factory=list)
    #: host seconds of each turn of the loop, and of each tick call in it
    turn_s: List[float] = dataclasses.field(default_factory=list)
    tick_s: List[float] = dataclasses.field(default_factory=list)

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    @property
    def admissions(self) -> int:
        return sum(1 for s in self.requests
                   if self.t0 < s.admit_launch < self.t1)

    def tokens_in_window(self) -> int:
        return sum(n for r in self.requests for t, n in r.marks
                   if self.t0 < t <= self.t1)


def program_config(conf: dict):
    """The program's model configuration from a benchmark config file."""
    from repro.models.config import ModelConfig
    return ModelConfig(name=conf["name"], family="dense",
                       n_layers=conf["n_layers"], d_model=conf["d_model"],
                       n_heads=conf["n_heads"], n_kv=conf["n_kv"],
                       d_ff=conf["d_ff"], vocab=conf["vocab"],
                       act="swiglu", rope_theta=conf["rope_theta"],
                       norm_eps=conf["norm_eps"], tie_embeddings=False,
                       compute_dtype=conf["dtype"])


def _annotate(traced: bool, name: str):
    return jax.profiler.TraceAnnotation(name) if traced \
        else contextlib.nullcontext()


class Cell:
    """One configuration under one mix, built and warmed (the set-up)."""

    def __init__(self, conf: dict, mix: dict, seed: int):
        from repro.launch.batching import BatchSpec, ContinuousBatcher
        from repro.reliability import parse_scheme
        t = time.perf_counter()
        #: seconds of each part of the set-up; "start" is when it began
        self.phases = {"start": t}
        self.conf, self.mix, self.seed = conf, mix, seed
        self.cfg = program_config(conf)
        self.spec = BatchSpec(slots=mix["slots"], chunk=mix["chunk"],
                              page_tokens=mix["page_tokens"],
                              prompt_buckets=tuple(mix["prompt_buckets"]),
                              gen_cap=mix["gen_cap"])
        b = ContinuousBatcher(self.cfg, parse_scheme(mix["scheme"]),
                              self.spec)
        p_bit = float(mix.get("inject_p_bit", 0.0))
        if mix.get("adaptive_scrub") and b.ecc is not None:
            from repro.runtime import AdaptiveScrub
            b.adaptive = AdaptiveScrub.from_prior(
                p_bit, b.pool.arena_spec.n_blocks, interval0=32)
        params = jax.block_until_ready(
            W.program_params(conf, seed, self.cfg.cdtype))
        t = self._phase("pool and weights", t)
        key = jnp.asarray(W.seed_words(seed))
        prep = b.prepare(params, key=key,
                         fault=SparseBitFlips(p_bit) if p_bit else None,
                         donate=True)
        del params
        jax.block_until_ready((prep, b.store))
        t = self._phase("prepare", t)
        self.batcher = b
        self.prep = prep
        self._warm()
        self._phase("warm-up", t)

    def _phase(self, name: str, since: float) -> float:
        now = time.perf_counter()
        self.phases[name] = now - since
        return now

    def _warm(self) -> None:
        """Compile and run once every program the mix uses: each prompt
        bucket's admission, the tick, a finishing fetch, and the pool
        scrub where the scheme has one."""
        from repro.launch.batching import Request
        b = self.batcher
        rng = np.random.default_rng(0)
        warm = [Request(WARM_RID + i,
                        rng.integers(0, self.cfg.vocab, n, dtype=np.int32),
                        2)
                for i, n in enumerate(self.spec.prompt_buckets)]
        b.run(warm)
        if b.ecc is not None:
            jax.block_until_ready(b.pool.scrub())
        jax.block_until_ready((b.pool.k, b.pool.v, b.store))

    def stats(self) -> Dict[str, int]:
        from repro.obs import fetch_telemetry
        s = fetch_telemetry({**self.prep, **self.batcher.telemetry()})
        return {k: int(np.asarray(v).sum()) for k, v in s.items()}

    # -- the window ---------------------------------------------------------

    def _send(self, w: Window, item_prompt, gen, due, rid) -> Sent:
        from repro.launch.batching import Request
        s = Sent(rid=rid, prompt=item_prompt, gen=gen, due=due)
        w.requests.append(s)
        try:
            self.batcher.submit(Request(rid, item_prompt, gen))
        except ValueError:
            w.failed += 1               # refused by admission
            return s
        s.sent = time.perf_counter()
        return s

    def _admit(self, traced: bool, pending: Dict[int, Sent],
               active: Dict[int, Sent]) -> None:
        """Admit what fits, FIFO; each admission launches when the one
        before it has its first token."""
        snap = [(req.rid, tl) for req, tl in self.batcher.queue]
        prev = time.perf_counter()
        with _annotate(traced, "bench.admit"):
            n = self.batcher.admit()
        for rid, tl in snap[:n]:
            pending[rid].admit_launch = prev
            prev = tl.marks[0][0]
            active[rid] = pending.pop(rid)

    def _tick(self, w: Window, traced: bool, active: Dict[int, Sent]):
        b, P = self.batcher, self.spec.chunk
        # a slot's cache holds its prompt and the tokens emitted before
        # this tick; at the middle of the chunk, (P - 1) / 2 more
        live = sum(len(s.prompt) + min(s.gen, 1 + P * s.ticks) - 1
                   + (P - 1) / 2.0 for s in active.values())
        t = time.perf_counter()
        with _annotate(traced, "bench.tick"):
            finished = b.tick()
        w.tick_s.append(time.perf_counter() - t)
        for s in active.values():
            s.ticks += 1
        w.live_kv_tokens.append(live)
        w.ticks += 1
        return finished

    def _collect(self, w: Window) -> None:
        res = self.batcher.results
        for s in w.requests:
            r = res.get(s.rid)
            if r is None:
                continue
            s.marks = list(r.timeline.marks)
            s.tokens = np.asarray(r.tokens)
        # requests sent but never delivered count as failed
        w.failed += sum(1 for s in w.requests
                        if s.tokens is None and not np.isnan(s.sent))

    def closed_loop(self, seconds: float, traced: bool = False,
                    on_close=None) -> Window:
        """`clients` callers, each sending its next request as soon as
        its last one finished; the slots are filled before the window."""
        mix = self.mix
        seqs = T.closed_items(mix, PER_CLIENT)
        flat = [it for seq in seqs for it in seq]
        prompts = T.prompt_tokens(self.seed, self.cfg.vocab,
                                  [it.prompt_len for it in flat])
        nxt = [0] * len(seqs)
        owner: Dict[int, int] = {}
        pending: Dict[int, Sent] = {}
        active: Dict[int, Sent] = {}
        idle = list(range(len(seqs)))
        w = Window(t0=0.0)

        def send_idle(now):
            while idle:
                c = idle.pop(0)
                rid = c * PER_CLIENT + nxt[c]
                it = seqs[c][nxt[c]]
                nxt[c] += 1
                s = self._send(w, prompts[rid], it.gen, now, rid)
                owner[rid] = c
                pending[rid] = s

        send_idle(time.perf_counter())
        self._admit(traced, pending, active)          # fill the slots
        w.t0 = time.perf_counter()
        deadline = w.t0 + seconds
        with _annotate(traced, "bench.window"):
            while (turn := time.perf_counter()) < deadline:
                send_idle(turn)
                self._admit(traced, pending, active)
                for r in self._tick(w, traced, active):
                    active.pop(r.rid)
                    idle.append(owner[r.rid])
                w.t1 = time.perf_counter()
                w.turn_s.append(w.t1 - turn)
        if on_close is not None:
            on_close()
        self.batcher.drain()
        self._collect(w)
        return w

    def open_loop(self, seconds: float, traced: bool = False,
                  on_close=None, rate_rps: float = 0.0) -> Window:
        """Poisson arrivals at the mix's rate, due on the host clock from
        the window's opening whether or not earlier ones have finished."""
        items = T.open_items(self.mix, seconds, rate_rps)
        prompts = T.prompt_tokens(self.seed, self.cfg.vocab,
                                  [it.prompt_len for it in items])
        b = self.batcher
        pending: Dict[int, Sent] = {}
        active: Dict[int, Sent] = {}
        w = Window(t0=time.perf_counter())
        deadline = w.t0 + seconds
        i = 0

        def send_due(now):
            nonlocal i
            while i < len(items) and w.t0 + items[i].due_s <= now:
                pending[i] = self._send(w, prompts[i], items[i].gen,
                                        w.t0 + items[i].due_s, i)
                i += 1

        with _annotate(traced, "bench.window"):
            while (turn := time.perf_counter()) < deadline:
                send_due(turn)
                if b.queue:
                    self._admit(traced, pending, active)
                if b.active:
                    for r in self._tick(w, traced, active):
                        active.pop(r.rid)
                elif i < len(items):
                    with _annotate(traced, "bench.wait"):
                        time.sleep(max(0.0, min(
                            w.t0 + items[i].due_s, deadline)
                            - time.perf_counter()))
                w.queue_after_turn.append(len(b.queue))
                w.t1 = time.perf_counter()
                w.turn_s.append(w.t1 - turn)
        # requests due before the deadline are still sent, late; the
        # window is closed after the drain, so that closing it (a trace
        # written out) delays no request's tokens
        send_due(deadline)
        b.drain()
        if on_close is not None:
            on_close()
        self._collect(w)
        return w

    def run_window(self, seconds: float, traced: bool = False,
                   on_close=None) -> Window:
        if self.mix["loop"] == "closed":
            return self.closed_loop(seconds, traced, on_close)
        if self.mix["loop"] == "open":
            return self.open_loop(seconds, traced, on_close)
        raise ValueError(f"unknown loop {self.mix['loop']!r}")

    def close(self) -> None:
        """Drop the program's state so that its memory can be reused."""
        self.batcher = None
        self.prep = None
