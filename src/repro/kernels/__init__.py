"""Pallas TPU kernels (Mosaic on a TPU, interpret mode on the CPU) + formats."""
from .formats import EdgeTileFormat, BsrFormat, build_edge_tiles, build_bsr
from .ops import (DeviceEdgeTiles, DeviceBsr, edge_spmv, bsr_spmv, seg_mm,
                  power_step, PsiKernelEngine, default_interpret)
from . import ref

__all__ = ["EdgeTileFormat", "BsrFormat", "build_edge_tiles", "build_bsr",
           "DeviceEdgeTiles", "DeviceBsr", "edge_spmv", "bsr_spmv", "seg_mm",
           "power_step", "PsiKernelEngine", "default_interpret", "ref"]
