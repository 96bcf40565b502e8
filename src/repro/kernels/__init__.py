# Pallas TPU kernels for the perf-critical paths the paper optimises:
#   scatter_apply  — rapid adapter switching (paper App. B `scatter_op`)
#   sidedelta      — per-request batched sparse side-delta (multi-tenant)
#   masked_update  — dense-mask fused apply (vectorised alternative)
#   sparse_adamw   — packed optimizer update (paper App. D)
#   flash_decode   — blocked decode attention (the serving hot loop)
# Validated against ref.py oracles in interpret mode (CPU container); the
# BlockSpecs target TPU VMEM tiling.
from repro.kernels import ops, ref  # noqa: F401
from repro.kernels.ops import (flash_decode, masked_update,  # noqa: F401
                               scatter_apply, sidedelta, sidedelta_table,
                               sparse_adamw)
