from .kway_merge import merge_tile_grid, sort_tile_rows
from .ops import kway_merge
from .ref import exact_starts_ref, kway_merge_ref

__all__ = ["exact_starts_ref", "kway_merge", "kway_merge_ref",
           "merge_tile_grid", "sort_tile_rows"]
