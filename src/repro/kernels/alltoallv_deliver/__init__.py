from .alltoallv_deliver import assemble_proc_tiles, deliver_tiles
from .ops import (
    assemble_proc_fused,
    check_fill_range,
    deliver,
    deliver_fused,
    deliver_packed,
    pack_slices,
    stage_slices,
    uses_pallas,
)

__all__ = [
    "assemble_proc_fused",
    "assemble_proc_tiles",
    "check_fill_range",
    "deliver",
    "deliver_fused",
    "deliver_packed",
    "deliver_tiles",
    "pack_slices",
    "stage_slices",
    "uses_pallas",
]
