from . import ref
from .ops import (gemm, spmm, sddmm, rmsnorm, agg_combine, flash_attention,
                  decode_attention, BITSTREAMS, program_config)

__all__ = ["ref", "gemm", "spmm", "sddmm", "rmsnorm", "agg_combine",
           "flash_attention", "decode_attention", "BITSTREAMS",
           "program_config"]
