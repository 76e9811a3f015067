"""One backend registry for every dispatchable op (DESIGN.md §12).

Before this module the repo had three uncoordinated dispatch mechanisms:
`ReliableStore(backend=...)` for the ECC kernels, `impl={scan,level,kernel}`
plus a netlist-specific env var, and the
per-module `interpret` plumbing of `kernels/`.  They are unified here as a
single table mapping op names to named implementations:

    op            implementations (default first)
    ------------  ---------------------------------
    diag_parity   kernel | jnp     encode/scrub the packed ECC arena
    inject_scrub  kernel | jnp     fused corrupt+scrub of the arena
    tmr_vote      kernel | jnp     per-bit 2-of-3 majority
    netlist_exec  level | scan | kernel   netlist execution engines
    crossbar_nor  kernel | jnp     gate-serial in-VMEM netlist interpreter

Resolution order for `resolve(op, impl)`:

1. the per-call ``impl=`` argument (threaded through by `Scheme`s and
   `multpim.execute_netlist`);
2. the ``REPRO_IMPL`` environment variable — either a bare implementation
   name applied to every op that has it (``REPRO_IMPL=jnp``) or a
   comma-separated list of ``op=impl`` pairs
   (``REPRO_IMPL=netlist_exec=kernel,diag_parity=jnp``);
3. the registered default.

Every implementation is registered as a lazy loader so importing this
module never drags in the Pallas kernel packages; `dispatch(op, impl)`
imports on first use and caches the resolved callable.

The Pallas interpret flag also lives here: `use_interpret()` follows the
platform — interpret mode on the CPU backend, where tests run the kernel
bodies as jnp, and compiled Mosaic kernels on a TPU — and
`kernels.use_interpret` delegates to it.
"""
from __future__ import annotations

import os
from types import SimpleNamespace
from typing import Callable, Dict, Optional, Tuple

import jax

__all__ = ["register", "ops", "implementations", "default_impl", "resolve",
           "dispatch", "use_interpret", "ENV_VAR"]

ENV_VAR = "REPRO_IMPL"

_LOADERS: Dict[str, Dict[str, Callable[[], Callable]]] = {}
_DEFAULTS: Dict[str, str] = {}
_CACHE: Dict[Tuple[str, str], Callable] = {}


def use_interpret() -> bool:
    """Run Pallas kernels in interpret mode?  Exactly when the default
    backend is the CPU: there is no switch that would let an accelerator
    run the interpreter in silence."""
    return jax.default_backend() == "cpu"


def register(op: str, impl: str, loader: Callable[[], Callable],
             default: bool = False) -> None:
    """Register implementation `impl` of `op` behind a zero-arg loader."""
    _LOADERS.setdefault(op, {})[impl] = loader
    if default or op not in _DEFAULTS:
        _DEFAULTS[op] = impl


def ops() -> Tuple[str, ...]:
    return tuple(sorted(_LOADERS))


def implementations(op: str) -> Tuple[str, ...]:
    if op not in _LOADERS:
        raise KeyError(f"unknown op {op!r} (registered: {ops()})")
    return tuple(_LOADERS[op])


def default_impl(op: str) -> str:
    implementations(op)          # raise on unknown op
    return _DEFAULTS[op]


def _env_overrides() -> Tuple[Dict[str, str], Optional[str]]:
    """Parse REPRO_IMPL into (op=impl pairs, bare token)."""
    pairs: Dict[str, str] = {}
    bare: Optional[str] = None
    for token in filter(None, (t.strip() for t in
                               os.environ.get(ENV_VAR, "").split(","))):
        if "=" in token:
            op, impl = token.split("=", 1)
            pairs[op.strip()] = impl.strip()
        else:
            bare = token
    return pairs, bare


def resolve(op: str, impl: Optional[str] = None) -> str:
    """Implementation name for `op`: per-call > REPRO_IMPL (pair, then bare
    token) > registered default."""
    avail = implementations(op)
    if impl is None:
        pairs, bare = _env_overrides()
        impl = pairs.get(op)
        if impl is None and bare in avail:
            impl = bare
    if impl is None:
        impl = _DEFAULTS[op]
    if impl not in avail:
        raise ValueError(f"unknown implementation {impl!r} for op {op!r} "
                         f"(available: {avail})")
    return impl


def dispatch(op: str, impl: Optional[str] = None) -> Callable:
    """Resolve and load the implementation of `op` (cached)."""
    name = resolve(op, impl)
    key = (op, name)
    if key not in _CACHE:
        _CACHE[key] = _LOADERS[op][name]()
    return _CACHE[key]


# --------------------------------------------------------------------------
# built-in registrations (lazy loaders; kernels import only on first use)
# --------------------------------------------------------------------------

def _load_diag_parity_kernel():
    from ..kernels.diag_parity import encode_parity, scrub, scrub_sharded

    def encode(buf, slopes=(1, 2, -1)):
        return encode_parity(buf, slopes=tuple(slopes))

    def scrub_(buf, parity, slopes=(1, 2, -1), mesh=None):
        if mesh is not None:
            return scrub_sharded(buf, parity, slopes=tuple(slopes), mesh=mesh)
        return scrub(buf, parity, slopes=tuple(slopes))

    return SimpleNamespace(encode=encode, scrub=scrub_)


def _load_diag_parity_jnp():
    from ..kernels.diag_parity import scrub_sharded
    from ..kernels.diag_parity.ref import encode_parity_ref, scrub_ref

    def encode(buf, slopes=(1, 2, -1)):
        return encode_parity_ref(buf, slopes=tuple(slopes))

    def scrub_(buf, parity, slopes=(1, 2, -1), mesh=None):
        def local(b, p):
            return scrub_ref(b, p, slopes=tuple(slopes))
        if mesh is not None:
            return scrub_sharded(buf, parity, slopes=tuple(slopes),
                                 mesh=mesh, local_scrub=local)
        return local(buf, parity)

    return SimpleNamespace(encode=encode, scrub=scrub_)


def _load_hsiao_secded_kernel():
    from ..kernels.hsiao_secded import encode_hsiao, scrub, scrub_sharded

    def encode(buf):
        return encode_hsiao(buf)

    def scrub_(buf, parity, mesh=None):
        if mesh is not None:
            return scrub_sharded(buf, parity, mesh=mesh)
        return scrub(buf, parity)

    return SimpleNamespace(encode=encode, scrub=scrub_)


def _load_hsiao_secded_jnp():
    from ..kernels.hsiao_secded import scrub_sharded
    from ..kernels.hsiao_secded.ref import encode_hsiao_ref, scrub_hsiao_ref

    def scrub_(buf, parity, mesh=None):
        if mesh is not None:
            return scrub_sharded(buf, parity, mesh=mesh,
                                 local_scrub=scrub_hsiao_ref)
        return scrub_hsiao_ref(buf, parity)

    return SimpleNamespace(encode=encode_hsiao_ref, scrub=scrub_)


def _load_inject_scrub_kernel():
    from ..kernels.inject_scrub import inject_scrub, inject_scrub_sharded

    def run(buf, parity, mask, slopes=(1, 2, -1), mesh=None):
        if mesh is not None:
            return inject_scrub_sharded(buf, parity, mask,
                                        slopes=tuple(slopes), mesh=mesh)
        return inject_scrub(buf, parity, mask, slopes=tuple(slopes))

    return run


def _load_inject_scrub_jnp():
    from ..kernels.inject_scrub import inject_scrub_sharded
    from ..kernels.inject_scrub.ref import inject_scrub_ref

    def run(buf, parity, mask, slopes=(1, 2, -1), mesh=None):
        def local(b, p, m):
            return inject_scrub_ref(b, p, m, slopes=tuple(slopes))
        if mesh is not None:
            return inject_scrub_sharded(buf, parity, mask,
                                        slopes=tuple(slopes), mesh=mesh,
                                        local_op=local)
        return local(buf, parity, mask)

    return run


def _load_tmr_vote_kernel():
    from ..kernels.tmr_vote import vote
    return vote


def _load_tmr_vote_jnp():
    from ..core.tmr import vote_array
    return vote_array


def _load_netlist_scan():
    from ..core.netlist import execute
    return execute


def _load_netlist_level():
    from ..core.scheduler import execute_levelized
    return execute_levelized


def _load_netlist_kernel():
    from ..kernels.netlist_exec import execute_packed
    return execute_packed


def _load_crossbar_nor_kernel():
    from ..kernels.crossbar_nor import execute_netlist
    return execute_netlist


def _load_crossbar_nor_jnp():
    from ..kernels.crossbar_nor.ref import execute_netlist_ref
    return execute_netlist_ref


register("diag_parity", "kernel", _load_diag_parity_kernel, default=True)
register("diag_parity", "jnp", _load_diag_parity_jnp)
register("hsiao_secded", "kernel", _load_hsiao_secded_kernel, default=True)
register("hsiao_secded", "jnp", _load_hsiao_secded_jnp)
register("inject_scrub", "kernel", _load_inject_scrub_kernel, default=True)
register("inject_scrub", "jnp", _load_inject_scrub_jnp)
register("tmr_vote", "kernel", _load_tmr_vote_kernel, default=True)
register("tmr_vote", "jnp", _load_tmr_vote_jnp)
register("netlist_exec", "level", _load_netlist_level, default=True)
register("netlist_exec", "scan", _load_netlist_scan)
register("netlist_exec", "kernel", _load_netlist_kernel)
register("crossbar_nor", "kernel", _load_crossbar_nor_kernel, default=True)
register("crossbar_nor", "jnp", _load_crossbar_nor_jnp)
