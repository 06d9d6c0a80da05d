# The harness's flops.py as it stood before eps trunks were resolved by name
# (chipbench/trunks/): test_chipbench_trunks.py holds the U-Net trunk's paths
# to it bit for bit. A frozen copy; do not edit.
"""Operations and bytes of the served work, from shapes alone.

``unet_flops`` counts the multiply-adds of one U-Net evaluation of one
image (2 operations each) in its convolutions, dense layers and attention
products, leaving out those that meet only zero padding; elementwise work
(GroupNorm, SiLU, softmax, additions) is left out, which puts the count
just under XLA's own cost analysis.
``step_kernel_bytes`` counts what one call of the per-row step kernel moves
through HBM, from the engine geometry: the slot-tile layout of
``slots`` states, each padded to whole 8-row granules of 256 lanes.
"""
from __future__ import annotations

from typing import Dict

TILE_C = 256      # lanes of one tile row
SUBLANE = 8       # row granule of one slot
COEF_COLS = 8     # per-row coefficient columns the kernel reads


def unet_flops(cfg: Dict) -> int:
    """Operations of one eps evaluation of one image."""
    H, W0, tdim = cfg["image_size"], cfg["base_width"], cfg["time_dim"]
    cin = cfg["in_channels"]

    def conv(k, ci, co, hw, stride=1):
        # multiply-adds that meet an input pixel: zero padding adds none.
        # Per axis: 3 taps per output less the 2 that fall off the edges
        # at stride 1, less the 1 at the bottom/right edge at stride 2.
        taps = {1: hw, 3: 3 * hw - 2}[k] if stride == 1 else 3 * hw - 1
        return 2 * ci * co * taps * taps

    def res(ci, co, hw):
        f = conv(3, ci, co, hw) + conv(3, co, co, hw) + 2 * tdim * co
        return f + (conv(1, ci, co, hw) if ci != co else 0)

    def attn(c, hw):
        L = hw * hw
        return 4 * 2 * L * c * c + 2 * 2 * L * L * c

    widths = [W0 * m for m in cfg["width_mults"]]
    f = 2 * W0 * tdim + 2 * tdim * tdim + conv(3, cin, W0, H)
    ch, hw, skips = W0, H, [W0]
    for lvl, w in enumerate(widths):
        for _ in range(cfg["n_res_blocks"]):
            f += res(ch, w, hw)
            if lvl in cfg["attn_levels"]:
                f += attn(w, hw)
            ch = w
            skips.append(ch)
        if lvl < len(widths) - 1:
            hw //= 2
            f += conv(3, ch, ch, hw, stride=2)
            skips.append(ch)
    f += 2 * res(ch, ch, hw) + attn(ch, hw)
    for lvl, w in reversed(list(enumerate(widths))):
        for _ in range(cfg["n_res_blocks"] + 1):
            f += res(ch + skips.pop(), w, hw)
            if lvl in cfg["attn_levels"]:
                f += attn(w, hw)
            ch = w
        if lvl > 0:
            hw *= 2
            f += conv(3, ch, ch, hw)
    return f + conv(3, ch, cin, hw)


def slot_rows(cfg: Dict) -> int:
    """Tile rows one slot's state takes (whole 8-row granules)."""
    n = cfg["image_size"] ** 2 * cfg["in_channels"]
    rows = -(-n // TILE_C)
    return -(-rows // SUBLANE) * SUBLANE


def step_kernel_bytes(cfg: Dict, slots: int, *, stochastic: bool,
                      preview: bool) -> int:
    """HBM bytes of one per-row step kernel call over ``slots`` slots:
    float32 state and eps in, state out, the x0 preview out when the tick
    makes previews, per-row coefficients in, and per-row noise seeds in
    when it is stochastic."""
    R = slots * slot_rows(cfg)
    tile = R * TILE_C * 4
    return (tile * (3 + (1 if preview else 0)) + R * COEF_COLS * 4
            + (R * 4 if stochastic else 0))
