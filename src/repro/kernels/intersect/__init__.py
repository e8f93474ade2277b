from .ops import (OP_AND, OP_ANDNOT, OP_OR, UnsupportedBackendError,
                  bitmap_to_docs, combine_batch, combine_cluster, compiles,
                  interpret_mode, intersect, launch_class,
                  pack_cluster_programs, pack_programs, postings_to_bitmap,
                  postings_to_bitmap_batch, unit_words, warm_shape_classes)
from .ref import (combine_batch_ref, combine_cluster_ref, intersect_ref,
                  popcount)

__all__ = ["OP_AND", "OP_ANDNOT", "OP_OR", "UnsupportedBackendError",
           "bitmap_to_docs", "combine_batch", "combine_cluster", "compiles",
           "interpret_mode", "intersect", "launch_class",
           "pack_cluster_programs", "pack_programs", "postings_to_bitmap",
           "postings_to_bitmap_batch", "unit_words", "warm_shape_classes",
           "combine_batch_ref", "combine_cluster_ref", "intersect_ref",
           "popcount"]
