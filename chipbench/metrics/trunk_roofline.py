"""The eps trunk's share of its roofline, in %: every slot's U-Net
evaluation per tick (slots x flops.unet_flops) over the trunk's device time
per tick times the bf16 peak. Trunk time is the tick program's device time
less the step kernel's (its one Pallas call, ``tpu_custom_call``). The
trunk is compute-bound (its weights are read once per tick: 0.3 GB
against 0.7 TFLOP)."""
import re

from flops import unet_flops

KERNEL = re.compile(r'custom_call_target="tpu_custom_call"')
TICK = re.compile(r"^jit_tick\b")


def read(run):
    t = run.trace
    if t is None or run.peaks is None:
        return None
    in_tick = lambda m: bool(TICK.search(m))
    ticks = t.module_count(in_tick)
    trunk = (t.op_seconds(lambda n: True, module=in_tick)
             - t.op_seconds(lambda n: bool(KERNEL.search(n)),
                            module=in_tick))
    if ticks <= 0 or trunk <= 0:
        return None
    per_tick = trunk / ticks
    return 100.0 * run.slots * unet_flops(run.config) / (
        per_tick * run.peaks["flops_bf16"])
