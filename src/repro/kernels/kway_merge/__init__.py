from .kway_merge import merge_tile_grid, sort_tile_rows
from .ops import kway_merge, unpack_runs
from .ref import (compact_tiles_ref, exact_starts_ref, kway_merge_ref,
                  unpack_runs_ref)

__all__ = ["compact_tiles_ref", "exact_starts_ref", "kway_merge",
           "kway_merge_ref", "merge_tile_grid", "sort_tile_rows",
           "unpack_runs", "unpack_runs_ref"]
