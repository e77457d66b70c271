from . import fused, ref
from .fused import (fused_prepare, fused_prepare_program, fused_prepare_start,
                    fused_prepare_wait)

__all__ = ["fused", "ref", "fused_prepare", "fused_prepare_program",
           "fused_prepare_start", "fused_prepare_wait"]
