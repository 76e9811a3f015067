"""Pallas TPU kernels for the perf-critical compute layers.

Each kernel package has:
  kernel.py — pl.pallas_call with explicit BlockSpec VMEM tiling (TPU target)
  ops.py    — jit'd public wrapper (handles layout/padding, interpret flag)
  ref.py    — pure-jnp oracle used by the allclose test sweeps

Interpret mode follows the platform: on the CPU backend (where the tests
run) the kernel bodies execute in the Pallas interpreter, and on a TPU they
compile to Mosaic.  The choice is owned by the backend registry
(reliability/backend.py, DESIGN.md §12); `use_interpret` here is a shim.
The `*_kernel` entry points default to compiled (`interpret=False`); the
ops wrappers pass `use_interpret()`.

Kernels:
  diag_parity     — rotate-XOR diagonal-parity encode (ECC hot loop, §IV)
  inject_scrub    — fused fault-inject → encode → syndrome → correct over
                    the packed arena (Monte-Carlo campaign hot loop, §VI)
  tmr_vote        — per-bit 2-of-3 majority voting (TMR hot loop, §V)
  crossbar_nor    — in-VMEM Min3 netlist interpreter, trials bit-packed in
                    uint32 lanes (the mMPU row-parallelism, §III); serial
                    in the gate dimension, fault-free only
  netlist_exec    — levelized netlist executor over the (L, W, 4) schedule
                    of core/scheduler.py: O(depth) wide steps, packed wire
                    state VMEM-resident across all levels, mask-based fault
                    injection bit-exact vs the scan reference (§VI-A)
  flash_attention — online-softmax blocked attention (model hot loop)
"""
from ..reliability.backend import use_interpret  # noqa: F401
