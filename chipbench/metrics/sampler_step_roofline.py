"""The step kernel's share of its roofline, in %: the bytes one call moves
(flops.step_kernel_bytes, from the engine geometry) times the calls in the
traced window, over the kernel's device time times the HBM bandwidth. The
kernel is memory-bound; its operations are a few per element.

The kernel is the one Pallas call (``tpu_custom_call``) in the tick
program: its op takes the jitted function's name (``%tick.1``), not the
kernel's."""
import re

from flops import step_kernel_bytes

KERNEL = re.compile(r'custom_call_target="tpu_custom_call"')
TICK = re.compile(r"^jit_tick\b")


def read(run):
    t = run.trace
    if t is None or run.peaks is None:
        return None
    kernel = lambda n: bool(KERNEL.search(n))
    in_tick = lambda m: bool(TICK.search(m))
    secs = t.op_seconds(kernel, module=in_tick)
    calls = t.op_count(kernel, module=in_tick)
    if secs <= 0 or calls <= 0:
        return None
    moved = calls * step_kernel_bytes(
        run.config, run.slots, stochastic=run.engine["stochastic"],
        preview=run.engine["preview"])
    return 100.0 * moved / (secs * run.peaks["hbm_bytes_per_s"])
