"""FooPar core: distributed-collection algebra, grids, cost model, algorithms.

The paper's primary contribution realized in JAX: DSeq (Table-1 op algebra),
GridN Cartesian process grids, the (t_s, t_w) cost model with TPU constants,
and the two paper algorithms (DNS matmul, Floyd-Warshall) built on them.
"""
from .dseq import (DSeq, spmd, reduce_d, shift_d, all_gather_d, all_to_all_d,
                   apply_d, scan_d, reduce_scatter_d, ring_shift_d,
                   all_gather_ring_d)
from .grid import GridN, Grid2D, Grid3D, make_grid_mesh
from . import costmodel
from .dns_matmul import dns_matmul, generic_matmul, dns_matmul_pallas
from .summa import (summa_matmul, cannon_matmul, summa_matmul_pallas,
                    cannon_matmul_pallas)
from .summa_pipelined import (summa_matmul_pipelined, cannon_matmul_25d,
                              summa_matmul_pipelined_pallas,
                              cannon_matmul_25d_pallas)
from .floyd_warshall import (floyd_warshall, blocked_floyd_warshall,
                             floyd_warshall_reference)
