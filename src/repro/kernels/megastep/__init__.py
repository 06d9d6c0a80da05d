from .ops import (DEFAULT_K_FUSE, MEGA_VMEM_BUDGET, TPU_REFUSAL, MegaSpec,
                  eligible, megastep_rows, megastep_tiles)

__all__ = ["DEFAULT_K_FUSE", "MEGA_VMEM_BUDGET", "MegaSpec", "TPU_REFUSAL",
           "eligible", "megastep_rows", "megastep_tiles"]
