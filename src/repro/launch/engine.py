"""Scan-compiled generation engine (DESIGN.md §13).

Replaces the interpreted serve loop (one jitted decode launch *per token*,
TMR as three *sequential* full generations, host syncs mid-hot-path) with
compiled generation under any protection scheme:

* **scan execution** — prefill + ``lax.scan`` over decode steps, so a whole
  ``gen``-token generation is one jitted launch; the KV-cache/token carry
  lives on device for the entire scan (XLA reuses the carry buffers
  in place — the donation the Python loop had to approximate per step).
* **copy axis** — TMR disciplines map onto real execution strategies
  instead of cost-model labels: the three (independently corrupted,
  per-copy ECC-scrubbed for `Compose`) stores are stacked on a leading
  copy axis; 'parallel'/'semi_parallel' ``vmap`` the generation over it
  (one batched launch), 'serial' re-runs the same compiled single-copy
  scan per copy (3x latency, but never 3x in-flight activations/cache —
  the paper's 1x-area property).
* **in-scan voting** — with ``vote_every=k`` the scan body votes the
  per-copy token ids (and, with ``vote_cache=True``, the KV caches) every
  k decode steps *before* divergence compounds; ``vote_every=0`` votes
  only the final token sequences, which is bit-exact against the legacy
  three-sequential-generations path under identical fault keys.
* **zero-sync telemetry** — every scrub/vote report stays on device as
  stacked counters inside the returned telemetry dict; `fetch_telemetry`
  performs the single host transfer after timing stops.
* **mesh execution** — constructed with ``mesh=``, the engine shards the
  store/caches/batch via the logical-axis rules, folds the TMR copy axis
  onto data-replica groups (`launch.mesh.fold_copy_axis`) so parallel
  disciplines reuse replicas that already exist (each group runs its copy
  as the single-copy program, voted at the end), and runs arena scrubs as
  per-shard shard_map launches with psum'd counters (DESIGN.md §14) —
  bit-exact against the single-device engine under identical fault keys.

Typical use (serve.py, serve_bench.py, examples/serve_tmr.py)::

    engine = GenerationEngine(cfg, scheme, gen=64)
    store, prep = engine.prepare(params, key=key, fault=model)
    tokens, telem = engine.generate(store, batch)     # compiled hot path
    stats = fetch_telemetry({**prep, **telem})        # ONE host sync

The interpreted reference survives as ``execution='loop'`` /
``generate_loop`` — the bit-exactness oracle and the benchmark baseline.
"""
from __future__ import annotations

import functools
from collections import OrderedDict
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..models.config import ModelConfig
from ..models.params import partition_specs
from ..models.steps import make_decode_step, make_prefill_step
from ..models.transformer import model_specs
from ..obs import LatencyTimeline, NULL_TRACER, Tracer
from ..obs import fetch_telemetry  # noqa: F401  (re-export: the PR-5 name;
#                                   now schema-validated by obs.registry)
from ..optim.sharding_rules import copy_stack_pspec
from ..pshard import DEFAULT_RULES, ShardingRules, use_mesh_and_rules
from ..reliability.scheme import (ArenaEcc, Compose, Scheme, Tmr,
                                  Unprotected)
from ..core import arena
from .mesh import fold_copy_axis

__all__ = ["GenerationEngine", "fetch_telemetry", "make_eval_hook"]


#: Donate on the CPU backend too.  Off by default, where donation saves
#: nothing worth its risk; tests turn it on so that a read of a donated
#: buffer fails on the CPU as it would on an accelerator.
DONATE_ON_CPU = False


def donate_argnums(*argnums: int) -> Tuple[int, ...]:
    """`argnums` off the CPU, where XLA reuses donated buffers in place;
    nothing on the CPU backend unless `DONATE_ON_CPU`."""
    return argnums if DONATE_ON_CPU or jax.default_backend() != "cpu" else ()


@functools.lru_cache(maxsize=None)
def _leaf_program(donate: Tuple[int, ...]):
    """The compiled program that builds one leaf of a serving store,
    ``(x, key, ecc, fault, dt) -> (leaf, (3,) counts or None)``: corrupt by
    `fault` (None: clean), then encode and scrub by `ecc` (None:
    unprotected), slab by slab.  `donate` is `donate_argnums(0)` when x's
    memory may become the leaf's, else ()."""
    def run(x, key, ecc, fault, dt):
        def slab(words, offset):
            dirty = words if fault is None \
                else fault.corrupt_words(words, key, dt, offset=offset)
            if ecc is None:
                return dirty, ()
            pad = (0, (-words.shape[0]) % arena.BLOCK)
            parity = ecc.encode_arena(jnp.pad(words, pad))
            fixed, _, counts = ecc.scrub_arena(jnp.pad(dirty, pad), parity)
            return fixed[:words.shape[0]], counts

        y, counts = arena.map_leaf_words(x, slab)
        return y, counts if ecc is not None else None

    return jax.jit(run, static_argnums=(2, 3, 4), donate_argnums=donate)


def _stack_leaf(copies, sharding=None) -> jax.Array:
    """Stack one leaf's three copies on the leading copy axis: on the
    default device, or — given the copy-sharded store `sharding` — straight
    from each copy's home device into that layout."""
    if sharding is None:
        return jnp.stack(copies)
    shape = (len(copies),) + copies[0].shape
    shards = []
    for dev, idx in sharding.addressable_devices_indices_map(shape).items():
        c = idx[0].start or 0
        piece = copies[c][idx[1:]] \
            if any(s != slice(None) for s in idx[1:]) else copies[c]
        shards.append(jax.device_put(piece[None], dev))
    return jax.make_array_from_single_device_arrays(shape, sharding, shards)


def _copy(stacked, i: int) -> Any:
    return jax.tree.map(lambda x: x[i], stacked)


def _disagreements(t3: jax.Array, axis=None) -> jax.Array:
    """Token positions where the three copies do not all agree (int32),
    in total or summed over `axis` of one copy (axis=0 of (3, B, gen)
    sequences: the per-step `tmr_step_disagreements` series)."""
    d = (t3[0] != t3[1]) | (t3[0] != t3[2]) | (t3[1] != t3[2])
    return d.sum(axis=axis, dtype=jnp.int32)


def _with_emitted(tokens: jax.Array,
                  telem: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    """Add the `tokens_emitted` counter (static shape — a host int wrapped
    as a device scalar, no device->host transfer)."""
    out = dict(telem)
    out["tokens_emitted"] = jnp.asarray(tokens.size, jnp.int32)
    return out


class GenerationEngine:
    """Compiled batched generation under a protection scheme.

    Parameters
    ----------
    cfg         : model config (any architecture family).
    scheme      : protection scheme; `Unprotected`, `DiagParityEcc`,
                  `Tmr(discipline)`, or `Compose` (None -> Unprotected).
    gen         : number of tokens to generate (prompt excluded).
    cache_len   : decode-cache length (default prompt_len + gen).
    vote_every  : TMR/Compose with a concurrent discipline (parallel/
                  semi) — vote the per-copy token ids every k decode
                  steps inside the scan (0 = vote only at the end;
                  bit-exact vs the legacy sequential path).
    vote_cache  : also vote the KV caches at each in-scan vote point
                  (requires vote_every > 0).
    execution   : 'scan' (compiled, default) or 'loop' (interpreted
                  reference) — what `generate()` dispatches to.
    mesh        : optional jax Mesh — shard the store, KV caches and
                  batch over it (DESIGN.md §14).  Concurrent TMR
                  disciplines fold the copy axis onto data replica groups
                  when `data % 3 == 0` (`launch.mesh.fold_copy_axis`);
                  arena scrubs run shard-wise with psum'd counters.
                  Bit-exact vs mesh=None under identical fault keys.
    rules       : ShardingRules for logical-axis resolution on `mesh`
                  (default DEFAULT_RULES).
    """

    def __init__(self, cfg: ModelConfig, scheme: Optional[Scheme] = None, *,
                 gen: int, cache_len: Optional[int] = None,
                 vote_every: int = 0, vote_cache: bool = False,
                 execution: str = "scan", mesh=None,
                 rules: Optional[ShardingRules] = None,
                 cost_spec=None):
        if execution not in ("scan", "loop"):
            raise ValueError(f"execution must be 'scan' or 'loop', "
                             f"got {execution!r}")
        self.cfg = cfg
        self.scheme = scheme if scheme is not None else Unprotected()
        if vote_every or vote_cache:
            # loud no-op guards: in-scan voting only exists on the scan
            # engine's concurrent copy-axis path
            if not isinstance(self.scheme, (Tmr, Compose)):
                raise ValueError("vote_every/vote_cache require a TMR or "
                                 "Compose scheme (no copy axis to vote over)")
            if execution == "loop":
                raise ValueError("in-scan voting requires execution='scan' "
                                 "(the loop reference votes final sequences "
                                 "only)")
            if vote_cache and not vote_every:
                raise ValueError("vote_cache needs vote_every > 0 (cache "
                                 "votes happen at the in-scan vote points)")
            if self._discipline() == "serial":
                raise ValueError("in-scan voting needs concurrently "
                                 "executing copies; the serial discipline "
                                 "re-runs them sequentially (use "
                                 "tmr-parallel/tmr-semi, or vote_every=0)")
        self.gen = int(gen)
        self.cache_len = cache_len
        self.vote_every = int(vote_every)
        self.vote_cache = bool(vote_cache)
        self.execution = execution
        self.mesh = mesh
        self.rules = rules if rules is not None else DEFAULT_RULES
        # optional mMPU cost projection (costmodel.DeviceSpec): when set,
        # telemetry gains mmpu_* gauges computed from a host-side event
        # stream compiled ONCE per batch geometry — no device work, no
        # per-token cost; None (the default) adds exactly nothing.
        self.cost_spec = cost_spec
        self._mmpu_cache: Dict[int, Any] = {}
        self._built: Dict[int, Any] = {}   # prompt_len -> compiled fns
        # chunk steps -> compiled fns; LRU-bounded (see _build_chunk):
        # _chunk_sizes buckets tails to powers of two so one engine serving
        # at one chunk size compiles at most 1 + log2(chunk) programs, and
        # the LRU cap bounds the cache across callers sweeping chunk sizes.
        self._chunk_built: "OrderedDict[int, Any]" = OrderedDict()

    # -- scheme plumbing ----------------------------------------------------

    @property
    def copy_axis(self) -> bool:
        """Does the store carry a leading 3-copy axis?"""
        return isinstance(self.scheme, (Tmr, Compose))

    def _tmr(self) -> Optional[Tmr]:
        if isinstance(self.scheme, Tmr):
            return self.scheme
        if isinstance(self.scheme, Compose):
            return self.scheme.tmr
        return None

    # -- mMPU cost projection (costmodel, DESIGN.md §17) --------------------

    def mmpu_projection(self, batch_size: int):
        """(event stream, MmpuCost) for one full generation at this batch
        geometry, or None without a cost_spec.  Compiled host-side and
        cached per batch size; `serve --mmpu-events` dumps the stream."""
        if self.cost_spec is None:
            return None
        key = int(batch_size)
        if key not in self._mmpu_cache:
            from .. import costmodel
            profile = costmodel.StepProfile.from_model_config(
                self.cfg, batch=key)
            stream = costmodel.scale_stream(
                costmodel.lower_step(self.scheme, profile, self.cost_spec),
                self.gen)
            cost = costmodel.fold(stream, self.cost_spec,
                                  tokens=key * self.gen)
            self._mmpu_cache[key] = (stream, cost)
        return self._mmpu_cache[key]

    def _finish_telemetry(self, tokens, telem):
        """tokens_emitted plus, when cost_spec is set, the mmpu_* gauges
        (host constants wrapped as device scalars — no transfers)."""
        out = _with_emitted(tokens, telem)
        proj = self.mmpu_projection(tokens.shape[0])
        if proj is not None:
            _, cost = proj
            out["mmpu_cycles_per_token"] = jnp.asarray(
                cost.cycles_per_token, jnp.float32)
            out["mmpu_energy_pj_per_token"] = jnp.asarray(
                cost.energy_pj_per_token, jnp.float32)
            out["mmpu_events"] = jnp.asarray(cost.n_events, jnp.int32)
        return out

    @property
    def _replicated(self) -> bool:
        """Do the copies run as replicas: each copy the single-copy
        program on its own copy group, voted at the end?  So on every
        copy-folded mesh unless the scan votes inside (`vote_every`), which
        needs all copies in one program.  A copy then computes bit for bit
        what its group computes for one model alone (a batched,
        partitioned program fuses and rounds differently on a TPU)."""
        return not self.vote_every and self._copy_meshes() is not None

    def _replicas(self, store) -> list:
        """Copy c of a copy-sharded stacked store as a (1, ...) array on
        copy group c's sub-mesh, made of the same device buffers."""
        def part(x, c, mesh):
            shards = [s.data for s in x.addressable_shards
                      if (s.index[0].start or 0) == c]
            spec = PartitionSpec(None, *x.sharding.spec[1:])
            return jax.make_array_from_single_device_arrays(
                (1,) + x.shape[1:], NamedSharding(mesh, spec), shards)
        return [jax.tree.map(lambda x: part(x, c, m), store)
                for c, m in enumerate(self._copy_meshes())]

    def _replica(self, f: Callable) -> Callable:
        """`f(params, ...)` as a replica program ``(c, part, ...)``: it
        takes copy c's (1, ...) part and traces under copy group c's
        sub-mesh — with no mesh at all for a one-device group, so that it
        is the plain single-chip program."""
        def run(c, part, *args):
            mesh = self._copy_meshes()[c]
            with use_mesh_and_rules(mesh if mesh.size > 1 else None,
                                    self.rules):
                return f(jax.tree.map(lambda a: a[0], part), *args)
        return run

    def _copy_batch(self, batch, c: int):
        """The batch placed on copy group c, as the replica programs take
        it."""
        mesh = self._copy_meshes()[c]
        if mesh.size == 1:
            return jax.device_put(batch, mesh.devices.flat[0])
        return self._shard_batch(batch, mesh)

    def _replica_vote(self, seqs) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """Vote per-copy (B, n) sequences gathered on one device."""
        seq3 = jnp.stack([jax.device_put(q, self._copy_devices()[0])
                          for q in seqs])
        tokens = self._tmr()._vote()(seq3[0], seq3[1], seq3[2])
        return tokens, {"tmr_step_disagreements": _disagreements(seq3, 0),
                        "tmr_final_disagreements": _disagreements(seq3)}

    def _voter(self) -> Callable:
        """The scheme's per-bit vote of three same-shape arrays.  On a
        multi-device exec mesh it runs per device inside `shard_map` on
        operands replicated to every device: XLA cannot partition a Mosaic
        kernel, and a vote needs all three copies in one place anyway."""
        vote = self._tmr()._vote()
        mesh = self.exec_mesh
        if mesh is None or mesh.size == 1:
            return vote
        return jax.shard_map(vote, mesh=mesh,
                             in_specs=(PartitionSpec(),) * 3,
                             out_specs=PartitionSpec(), check_vma=False)

    def _discipline(self) -> Optional[str]:
        tmr = self._tmr()
        return tmr.discipline if tmr is not None else None

    # -- mesh plumbing (DESIGN.md §14) --------------------------------------

    @property
    def exec_mesh(self):
        """Mesh the compiled programs actually run under.  Concurrent TMR
        disciplines fold the copy axis onto data replica groups when the
        data axis can host the three copies; the serial discipline (one
        copy in flight at a time) and non-copy schemes keep the
        constructor mesh."""
        if self.mesh is None:
            return None
        if self.copy_axis and self._discipline() != "serial":
            folded = fold_copy_axis(self.mesh)
            if folded is not None:
                return folded
        return self.mesh

    def _param_shardings(self, stacked: bool):
        """NamedSharding tree for the serving store on the exec mesh:
        `partition_specs` resolution of the model's logical axes, with the
        leading 3-copy axis prepended (sharded over the "copy" axis on a
        folded mesh, replicated otherwise) when `stacked`."""
        mesh = self.exec_mesh
        pspecs = partition_specs(model_specs(self.cfg), mesh, self.rules)
        if stacked:
            pspecs = jax.tree.map(
                lambda s: copy_stack_pspec(s, mesh, rules=self.rules), pspecs)
        return jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs)

    def shard_store(self, store: Any) -> Any:
        """Place a prepared store on the engine's exec mesh (no-op without
        one).  `prepare` calls this; it is public so externally built
        stores (checkpoint restores) can be placed the same way."""
        if self.mesh is None:
            return store
        return jax.device_put(
            store, self._param_shardings(stacked=self.copy_axis))

    def _shard_batch(self, batch: Dict[str, jax.Array],
                     mesh=None) -> Dict[str, jax.Array]:
        """The batch over `mesh` (default: the exec mesh, if any)."""
        mesh = mesh if mesh is not None else self.exec_mesh
        if mesh is None:
            return batch
        from ..pshard import spec_for
        return {
            k: jax.device_put(v, NamedSharding(mesh, spec_for(
                v.shape, ("batch",) + (None,) * (v.ndim - 1), mesh,
                self.rules)))
            for k, v in batch.items()}

    def _copy_meshes(self) -> Optional[list]:
        """Each TMR copy's group of a copy-folded exec mesh, as a mesh over
        the remaining axes; None on any other mesh."""
        mesh = self.exec_mesh
        if mesh is None or "copy" not in mesh.axis_names:
            return None
        ax = mesh.axis_names.index("copy")
        names, types = zip(*[(n, t) for n, t in zip(mesh.axis_names,
                                                    mesh.axis_types)
                             if n != "copy"])
        return [Mesh(np.take(mesh.devices, c, axis=ax), names,
                     axis_types=types) for c in range(mesh.shape["copy"])]

    def _copy_devices(self):
        """One home device per TMR copy on a copy-folded exec mesh (the
        first device of each copy group), else None."""
        meshes = self._copy_meshes()
        return meshes and [m.devices.flat[0] for m in meshes]

    def prepare(self, params: Any, key: Optional[jax.Array] = None,
                fault=None, dt: float = 1.0, *, donate: bool = False
                ) -> Tuple[Any, Dict[str, Any]]:
        """Build the scheme's serving store from clean params.

        Applies one exposure interval of `fault` to every held data copy
        (copy i under ``fold_in(key, 100 + i)`` — the serve-driver key
        convention, so engine stores are bit-identical to the legacy
        driver's under the same seed), then applies the scheme's
        *storage-side* protection: ECC schemes scrub the corrupted
        store(s), and TMR schemes stack the copies on the leading copy
        axis.

        The store is built leaf by leaf — encode, corrupt and scrub of one
        leaf in one compiled program — so the model is never held packed,
        corrupted or unpacked as a whole beside itself.  Because every leaf
        starts on an ECC block boundary, the per-leaf scrubs are exactly
        the arena-wide scrub: same bits, same counts.  With `donate`, the
        programs take each params leaf's memory for the store off the CPU,
        so that one model's worth of memory holds both: the caller must not
        read `params` after the call.  Without it, `params` stays intact.

        Returns (store, prep_telemetry); the telemetry values are
        on-device scalars (fetch once via `fetch_telemetry`).

        With a mesh, the finished store is placed by `shard_store`.  On a
        copy-folded mesh each copy is built on its own copy group's device
        and the stacked store is assembled from those shards, so no device
        ever holds more than one copy.
        """
        scheme = self.scheme
        ecc = scheme if isinstance(scheme, ArenaEcc) \
            else scheme.ecc if isinstance(scheme, Compose) else None
        n_copies = 3 if self.copy_axis else 1
        homes = self._copy_devices()
        shardings = homes and jax.tree.leaves(
            self._param_shardings(stacked=True))
        leaves, treedef = jax.tree.flatten(params)
        keys = [jax.random.split(jax.random.fold_in(key, 100 + i),
                                 len(leaves)) if fault is not None else None
                for i in range(n_copies)]
        out, counts = [], []
        for j, x in enumerate(leaves):
            copies = [None] * n_copies
            for i in reversed(range(n_copies)):   # copy 0 may consume x
                moved = homes is not None and homes[i] not in x.devices()
                xi = jax.device_put(x, homes[i]) if moved else x
                if fault is None and ecc is None:
                    y, c = xi, None
                else:
                    # a copy moved to its home device is the engine's own
                    own = moved or (donate and i == 0)
                    y, c = _leaf_program(donate_argnums(0) if own else ())(
                        xi, None if keys[i] is None else keys[i][j], ecc,
                        fault, dt)
                copies[i] = y
                if c is not None:
                    counts.append(c)
            out.append(copies[0] if not self.copy_axis
                       else _stack_leaf(copies, shardings and shardings[j]))
        store = self.shard_store(treedef.unflatten(out))
        if ecc is None:
            return store, {}
        total = sum(jax.device_put(c, jax.devices()[0]) for c in counts)
        return store, {"ecc_corrected": total[0],
                       "ecc_parity_fixed": total[1],
                       "ecc_uncorrectable": total[2]}

    # -- compiled paths -----------------------------------------------------

    def _build(self, prompt_len: int):
        if prompt_len in self._built:
            return self._built[prompt_len]
        cfg, gen = self.cfg, self.gen
        cache_len = self.cache_len or (prompt_len + gen)
        prefill = make_prefill_step(cfg, cache_len=cache_len)
        decode = make_decode_step(cfg)
        tmr = self._tmr()
        vote = self._voter() if tmr is not None else None
        vote_every, vote_cache = self.vote_every, self.vote_cache

        def single_scan(params, batch):
            tok0, _, cache = prefill(params, batch)
            if gen == 1:
                return tok0, {}

            def body(carry, _):
                tok, cache = carry
                ntok, _, cache = decode(params, tok, cache)
                return (ntok, cache), ntok

            _, toks = jax.lax.scan(body, (tok0, cache), None, length=gen - 1)
            # toks (gen-1, B, 1) -> (B, gen-1); tok0 (B, 1)
            return jnp.concatenate([tok0, toks[:, :, 0].T], axis=1), {}

        # concurrent copy-axis evaluator for 'parallel'/'semi_parallel':
        # vmap prefill+scan over the stacked copies (one batched launch).
        # On a copy-folded mesh it runs only when the scan votes inside
        # (vote_every): the stacked axis is sharded over three disjoint
        # replica groups — each group runs ONE copy — and the per-step
        # vote/disagreement reads become tiny cross-replica collectives on
        # the token ids (DESIGN.md §14); otherwise the copies run as
        # replicas (`_replicated`).  The
        # 'serial' discipline never enters this path — it re-runs the
        # single-copy scan per copy (generate_scan), keeping the paper's
        # 1x-area property: no 3x activations/cache in flight.
        def tmr_scan(stacked, batch):
            tok3, _, cache3 = jax.vmap(
                lambda p: prefill(p, batch))(stacked)

            def body(carry, step):
                tok3, cache3 = carry
                ntok3, _, cache3 = jax.vmap(decode)(stacked, tok3, cache3)
                dis = _disagreements(ntok3)
                if vote_every:
                    do = (step + 1) % vote_every == 0
                    voted = vote(ntok3[0], ntok3[1], ntok3[2])
                    ntok3 = jnp.where(do, voted[None], ntok3)
                    if vote_cache:
                        cache3 = jax.lax.cond(
                            do,
                            lambda c: jax.tree.map(
                                lambda x: jnp.broadcast_to(
                                    vote(x[0], x[1], x[2])[None],
                                    x.shape).astype(x.dtype), c),
                            lambda c: c, cache3)
                return (ntok3, cache3), (ntok3, dis)

            telem: Dict[str, jax.Array] = {}
            if gen == 1:
                seq3 = tok3
                telem["tmr_step_disagreements"] = \
                    _disagreements(tok3)[None]
            else:
                _, (steps3, dis) = jax.lax.scan(
                    body, (tok3, cache3), jnp.arange(gen - 1))
                # (gen-1, 3, B, 1) + (3, B, 1) -> per-copy (3, B, gen)
                seq3 = jnp.concatenate([tok3[None], steps3], axis=0)
                seq3 = jnp.moveaxis(seq3[..., 0], 0, -1)
                telem["tmr_step_disagreements"] = jnp.concatenate(
                    [_disagreements(tok3)[None], dis])
            out = vote(seq3[0], seq3[1], seq3[2])
            telem["tmr_final_disagreements"] = _disagreements(seq3)
            return out, telem

        def tmr_prefill(stacked, batch):
            return jax.vmap(lambda p: prefill(p, batch))(stacked)

        # donation: the Python-loop path re-launches decode per token; on
        # accelerators the cache carry is donated so each step updates the
        # KV buffers in place (`donate_argnums`: off the CPU only)
        donate = donate_argnums(2)
        concurrent = tmr is not None and tmr.discipline != "serial"
        replicas = self._replicated
        vmapped = concurrent and not replicas
        fns = {
            "prefill": jax.jit(prefill),
            "decode": jax.jit(decode, donate_argnums=donate),
            "single_scan": jax.jit(single_scan),
            "tmr_prefill": jax.jit(tmr_prefill) if vmapped else None,
            "tmr_scan": jax.jit(tmr_scan) if vmapped else None,
            # (c, copy c's part, ...): the single-copy program on copy
            # group c
            "replica_prefill": (jax.jit(self._replica(prefill),
                                        static_argnums=0)
                                if replicas else None),
            "replica_scan": (jax.jit(self._replica(single_scan),
                                     static_argnums=0)
                             if replicas else None),
        }
        self._built[prompt_len] = fns
        return fns

    def _build_chunk(self, n: int):
        """Compiled decode-chunk programs: `n` scan steps from a (token,
        cache) carry.  Independent of prompt length (the cache shapes are
        traced), so keyed by chunk size only.  The TMR chunk takes the
        global step `offset` as a *traced* scalar, so the in-scan vote
        schedule `(step + 1) % vote_every == 0` lines up with the
        unchunked scan bit for bit at any chunk size — no recompile per
        chunk position."""
        if n in self._chunk_built:
            self._chunk_built.move_to_end(n)   # LRU touch
            return self._chunk_built[n]
        decode = make_decode_step(self.cfg)
        tmr = self._tmr()
        vote = self._voter() if tmr is not None else None
        vote_every, vote_cache = self.vote_every, self.vote_cache

        def chunk_scan(params, tok, cache):
            def body(carry, _):
                tok, cache = carry
                ntok, _, cache = decode(params, tok, cache)
                return (ntok, cache), ntok

            (tok, cache), toks = jax.lax.scan(body, (tok, cache), None,
                                              length=n)
            # toks (n, B, 1) -> (B, n)
            return tok, cache, toks[:, :, 0].T

        def tmr_chunk(stacked, tok3, cache3, offset):
            # identical body to _build's tmr_scan, stepped from `offset`
            def body(carry, step):
                tok3, cache3 = carry
                ntok3, _, cache3 = jax.vmap(decode)(stacked, tok3, cache3)
                dis = _disagreements(ntok3)
                if vote_every:
                    do = (step + 1) % vote_every == 0
                    voted = vote(ntok3[0], ntok3[1], ntok3[2])
                    ntok3 = jnp.where(do, voted[None], ntok3)
                    if vote_cache:
                        cache3 = jax.lax.cond(
                            do,
                            lambda c: jax.tree.map(
                                lambda x: jnp.broadcast_to(
                                    vote(x[0], x[1], x[2])[None],
                                    x.shape).astype(x.dtype), c),
                            lambda c: c, cache3)
                return (ntok3, cache3), (ntok3, dis)

            (tok3, cache3), (steps3, dis) = jax.lax.scan(
                body, (tok3, cache3), offset + jnp.arange(n))
            return tok3, cache3, steps3, dis

        donate = donate_argnums(2)
        concurrent = tmr is not None and tmr.discipline != "serial"
        replicas = self._replicated
        fns = {
            "chunk": jax.jit(chunk_scan, donate_argnums=donate),
            "replica_chunk": (jax.jit(self._replica(chunk_scan),
                                      static_argnums=0,
                                      donate_argnums=donate_argnums(3))
                              if replicas else None),
            "tmr_chunk": (jax.jit(tmr_chunk, donate_argnums=donate)
                          if concurrent and not replicas else None),
        }
        self._chunk_built[n] = fns
        while len(self._chunk_built) > self.CHUNK_CACHE_MAX:
            self._chunk_built.popitem(last=False)   # evict least recent
        assert len(self._chunk_built) <= self.CHUNK_CACHE_MAX
        return fns

    #: compiled-chunk cache bound: generous vs the <= 1 + log2(chunk)
    #: sizes one serving configuration produces, small enough that a
    #: caller sweeping chunk sizes can't grow the cache without bound.
    CHUNK_CACHE_MAX = 8

    def _chunk_sizes(self, chunk: int):
        """Chunk-size schedule for `gen - 1` decode steps: full `chunk`
        launches, then the tail bucketed into descending powers of two —
        every size drawn from {chunk} | {2^k < chunk}, so varying `gen`
        at a fixed chunk size reuses at most 1 + log2(chunk) compiled
        programs instead of compiling one per distinct tail."""
        rem = self.gen - 1
        while rem >= chunk:
            yield chunk
            rem -= chunk
        if rem > 0:
            p = 1 << (rem.bit_length() - 1)
            while rem > 0:
                if rem >= p:
                    yield p
                    rem -= p
                p >>= 1

    # -- public entry points ------------------------------------------------

    def generate(self, store: Any, batch: Dict[str, jax.Array]
                 ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """Generate `gen` tokens: (tokens (B, gen) int32, telemetry).

        Dispatches on the configured execution mode; telemetry values are
        on-device counters (single fetch via `fetch_telemetry`)."""
        if self.execution == "loop":
            return self.generate_loop(store, batch)
        return self.generate_scan(store, batch)

    def generate_scan(self, store, batch):
        """The compiled path: one jitted prefill+scan launch per copy —
        one total for single stores and the vmapped parallel/semi copy
        axis; the serial discipline re-runs the same compiled program per
        copy (3x latency, 1x in-flight activations/cache) and votes the
        three token sequences."""
        with use_mesh_and_rules(self.exec_mesh, self.rules):
            batch = self._shard_batch(batch)
            fns = self._build(batch["tokens"].shape[1])
            if not self.copy_axis:
                tokens, telem = fns["single_scan"](store, batch)
            elif self._discipline() == "serial":
                outs = [fns["single_scan"](_copy(store, i), batch)[0]
                        for i in range(3)]
                tokens = self._voter()(*outs)
                telem = {"tmr_final_disagreements":
                         _disagreements(jnp.stack(outs))}
            elif self._replicated:
                tokens, telem = self._replica_vote([
                    fns["replica_scan"](c, p, self._copy_batch(batch, c))[0]
                    for c, p in enumerate(self._replicas(store))])
            else:
                tokens, telem = fns["tmr_scan"](store, batch)
            return tokens, self._finish_telemetry(tokens, telem)

    def generate_chunked(self, store, batch, *, chunk: int,
                         timeline: Optional[LatencyTimeline] = None,
                         tracer: Tracer = NULL_TRACER
                         ) -> Tuple[jax.Array, Dict[str, jax.Array],
                                    LatencyTimeline]:
        """Latency-observable generation: the scan split into compiled
        chunk launches, a `LatencyTimeline` mark after each one lands.

        Bit-exact against `generate_scan` under every scheme and
        `vote_every` (the chunk programs thread the global step offset, so
        the in-scan vote schedule is unchanged).  Each mark is a
        `jax.block_until_ready` + `perf_counter` read — a sync point, NOT
        a device->host data transfer; telemetry stays on device and
        `fetch_telemetry` remains the single host sync.

        The first mark is TTFT (prefill -> first token); subsequent marks
        time each `chunk`-token launch, feeding `timeline.tpot_samples()`.
        The serial discipline runs copies 0 and 1 to completion first
        (preserving the 1x in-flight property), so its marks — and its
        honest TTFT — start at the third copy's prefill, when voted
        tokens first exist.

        Returns (tokens, telemetry, timeline).
        """
        if chunk < 1:
            raise ValueError(f"chunk must be >= 1, got {chunk}")
        if self.execution == "loop":
            raise ValueError("chunked generation requires execution='scan' "
                             "(the loop reference is already per-token)")
        timeline = timeline if timeline is not None else LatencyTimeline()
        with use_mesh_and_rules(self.exec_mesh, self.rules):
            batch = self._shard_batch(batch)
            fns = self._build(batch["tokens"].shape[1])
            timeline.begin()
            if not self.copy_axis:
                with tracer.trace("prefill", tokens=1):
                    tok, _, cache = fns["prefill"](store, batch)
                    jax.block_until_ready(tok)
                timeline.mark(1)
                parts = [tok]
                for n in self._chunk_sizes(chunk):
                    with tracer.trace("decode_chunk", tokens=n):
                        tok, cache, toks = self._build_chunk(n)["chunk"](
                            store, tok, cache)
                        jax.block_until_ready(toks)
                    timeline.mark(n)
                    parts.append(toks)
                tokens = jnp.concatenate(parts, axis=1)
                telem: Dict[str, jax.Array] = {}
            elif self._discipline() == "serial":
                tokens, telem = self._chunked_serial(
                    store, batch, fns, chunk, timeline, tracer)
            else:
                tokens, telem = self._chunked_concurrent(
                    store, batch, fns, chunk, timeline, tracer)
            return tokens, self._finish_telemetry(tokens, telem), timeline

    def _chunked_concurrent(self, store, batch, fns, chunk, timeline,
                            tracer):
        """Chunked 'parallel'/'semi' TMR: vmapped prefill + chunked vmapped
        scans; per-step disagreements and vote points identical to the
        unchunked tmr_scan (global-step offset threading)."""
        if self._replicated:
            return self._chunked_replicas(self._replicas(store), batch, fns,
                                          chunk, timeline, tracer)
        vote = self._voter()
        with tracer.trace("tmr_prefill", tokens=1):
            tok3, _, cache3 = fns["tmr_prefill"](store, batch)
            jax.block_until_ready(tok3)
        timeline.mark(1)
        seq_parts = [tok3[None]]                       # (1, 3, B, 1)
        dis_parts = [_disagreements(tok3)[None]]
        off = 0
        for n in self._chunk_sizes(chunk):
            with tracer.trace("tmr_decode_chunk", tokens=n, offset=off):
                tok3, cache3, steps3, dis = \
                    self._build_chunk(n)["tmr_chunk"](
                        store, tok3, cache3, jnp.int32(off))
                jax.block_until_ready(steps3)
            timeline.mark(n)
            seq_parts.append(steps3)
            dis_parts.append(dis)
            off += n
        # (gen, 3, B, 1) -> per-copy (3, B, gen), as in tmr_scan
        seq3 = jnp.concatenate(seq_parts, axis=0)
        seq3 = jnp.moveaxis(seq3[..., 0], 0, -1)
        tokens = vote(seq3[0], seq3[1], seq3[2])
        return tokens, {
            "tmr_step_disagreements": jnp.concatenate(dis_parts),
            "tmr_final_disagreements": _disagreements(seq3)}

    def _chunked_replicas(self, parts, batch, fns, chunk, timeline,
                          tracer):
        """Chunked TMR with one replica per copy group: each chunk
        launches on all copy groups at once, then the per-copy sequences
        are voted on one device."""
        with tracer.trace("tmr_prefill", tokens=1):
            outs = [fns["replica_prefill"](c, p, self._copy_batch(batch, c))
                    for c, p in enumerate(parts)]
            toks = [o[0] for o in outs]
            caches = [o[2] for o in outs]
            jax.block_until_ready(toks)
        timeline.mark(1)
        seqs = [[t] for t in toks]
        for n in self._chunk_sizes(chunk):
            with tracer.trace("tmr_decode_chunk", tokens=n):
                for c, p in enumerate(parts):
                    toks[c], caches[c], steps = \
                        self._build_chunk(n)["replica_chunk"](
                            c, p, toks[c], caches[c])
                    seqs[c].append(steps)
                jax.block_until_ready([q[-1] for q in seqs])
            timeline.mark(n)
        return self._replica_vote(
            [jnp.concatenate(q, axis=1) for q in seqs])

    def _chunked_serial(self, store, batch, fns, chunk, timeline, tracer):
        """Chunked serial TMR: copies 0/1 run to completion (sequentially,
        no marks — only their token sequences are kept), then copy 2's
        launches each complete a *voted* chunk (majority vote is
        elementwise, so chunk-wise voting equals the final-sequence
        vote)."""
        vote = self._voter()
        per_copy = []                      # copies 0, 1: [tok0, chunk, ...]
        for i in range(2):
            params = _copy(store, i)
            with tracer.trace(f"serial_copy{i}", copy=i):
                tok, _, cache = fns["prefill"](params, batch)
                parts = [tok]
                for n in self._chunk_sizes(chunk):
                    tok, cache, toks = self._build_chunk(n)["chunk"](
                        params, tok, cache)
                    parts.append(toks)
            per_copy.append(parts)
        params = _copy(store, 2)
        with tracer.trace("serial_copy2_prefill", tokens=1):
            tok, _, cache = fns["prefill"](params, batch)
            voted = vote(per_copy[0][0], per_copy[1][0], tok)
            jax.block_until_ready(voted)
        timeline.mark(1)
        parts2, voted_parts = [tok], [voted]
        for idx, n in enumerate(self._chunk_sizes(chunk), start=1):
            with tracer.trace("serial_decode_chunk", tokens=n, copy=2):
                tok, cache, toks = self._build_chunk(n)["chunk"](
                    params, tok, cache)
                v = vote(per_copy[0][idx], per_copy[1][idx], toks)
                jax.block_until_ready(v)
            timeline.mark(n)
            parts2.append(toks)
            voted_parts.append(v)
        tokens = jnp.concatenate(voted_parts, axis=1)
        seq3 = jnp.stack([jnp.concatenate(p, axis=1)
                          for p in (per_copy[0], per_copy[1], parts2)])
        return tokens, {"tmr_final_disagreements": _disagreements(seq3)}

    def generate_loop(self, store, batch):
        """Interpreted reference: jitted prefill + per-token decode
        launches; TMR as three sequential full generations with one final
        vote (the legacy serving path — the bit-exactness oracle)."""
        with use_mesh_and_rules(self.exec_mesh, self.rules):
            batch = self._shard_batch(batch)
            fns = self._build(batch["tokens"].shape[1])

            def one(params):
                tok, _, cache = fns["prefill"](params, batch)
                toks = [tok]
                for _ in range(self.gen - 1):
                    tok, _, cache = fns["decode"](params, tok, cache)
                    toks.append(tok)
                return jnp.concatenate(toks, axis=1)

            if not self.copy_axis:
                tokens = one(store)
                return tokens, self._finish_telemetry(tokens, {})
            outs = [one(_copy(store, i)) for i in range(3)]
            seq3 = jnp.stack(outs)
            voted = self._voter()(*outs)
            return voted, self._finish_telemetry(
                voted, {"tmr_final_disagreements": _disagreements(seq3)})

    def ttft(self, store, batch) -> jax.Array:
        """First generated token(s) only — the prefill launch.  Time this
        (after warmup) for time-to-first-token."""
        with use_mesh_and_rules(self.exec_mesh, self.rules):
            batch = self._shard_batch(batch)
            fns = self._build(batch["tokens"].shape[1])
            if not self.copy_axis:
                tok, _, _ = fns["prefill"](store, batch)
                return tok
            if self._discipline() == "serial":
                toks = [fns["prefill"](_copy(store, i), batch)[0]
                        for i in range(3)]
            elif self._replicated:
                return self._replica_vote([
                    fns["replica_prefill"](c, p,
                                           self._copy_batch(batch, c))[0]
                    for c, p in enumerate(self._replicas(store))])[0]
            else:
                tok3, _, _ = fns["tmr_prefill"](store, batch)
                toks = [tok3[0], tok3[1], tok3[2]]
            return self._voter()(*toks)


def make_eval_hook(engine: GenerationEngine, batch: Dict[str, jax.Array]
                   ) -> Callable[[Any, int], Dict[str, Any]]:
    """A `TrainLoop` eval hook: compiled generation from the current params.

    The loop's scheme has already scrubbed/voted the store before the hook
    fires, so the hook runs the engine's single-copy scan path on the plain
    params — one launch per eval, tokens left on device (the loop keeps
    them in `eval_history`; fetch after training)."""
    def eval_fn(params: Any, step: int) -> Dict[str, Any]:
        with use_mesh_and_rules(engine.exec_mesh, engine.rules):
            fns = engine._build(batch["tokens"].shape[1])
            tokens, _ = fns["single_scan"](params, batch)
        return {"step": step, "tokens": tokens}

    return eval_fn
