"""The paper's five algorithms (Section 3), and Graphalytics CDLP, as GraphMat
vertex programs."""

from repro.algos.pagerank import pagerank, pagerank_program  # noqa: F401
from repro.algos.bfs import bfs, bfs_program  # noqa: F401
from repro.algos.cdlp import cdlp, cdlp_program  # noqa: F401
from repro.algos.sssp import sssp, sssp_program  # noqa: F401
from repro.algos.triangle_count import triangle_count  # noqa: F401
from repro.algos.collab_filter import collaborative_filtering  # noqa: F401
from repro.algos.multi import (multi_bfs, multi_sssp,  # noqa: F401
                               personalized_pagerank)
