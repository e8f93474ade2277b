from .ops import (OP_AND, OP_ANDNOT, OP_OR, UnsupportedBackendError,
                  bitmap_to_docs, combine_batch, combine_cluster,
                  interpret_mode, intersect, intersect_batch,
                  pack_cluster_programs, pack_programs, pad_to_shape_class,
                  postings_to_bitmap, postings_to_bitmap_batch)
from .ref import (combine_batch_ref, combine_cluster_ref, intersect_batch_ref,
                  intersect_ref, popcount)

__all__ = ["OP_AND", "OP_ANDNOT", "OP_OR", "UnsupportedBackendError",
           "bitmap_to_docs", "combine_batch", "combine_cluster",
           "interpret_mode", "intersect",
           "intersect_batch", "pack_cluster_programs", "pack_programs",
           "pad_to_shape_class", "postings_to_bitmap",
           "postings_to_bitmap_batch", "combine_batch_ref",
           "combine_cluster_ref", "intersect_batch_ref", "intersect_ref",
           "popcount"]
