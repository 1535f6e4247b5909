"""Hand-optimized "native" baselines (the paper's Table-3 foil).

Direct jnp implementations of each algorithm (CDLP's in numpy) with no
framework machinery: no GraphProgram dispatch, no property pytrees, no
frontier bookkeeping beyond what the algorithm itself needs.  The gap framework-vs-native measured by
``benchmarks/bench_native_gap.py`` reproduces the paper's 1.2× claim
qualitatively on this host.
"""

from repro.algos.native.baselines import (  # noqa: F401
    native_bfs, native_cdlp, native_cf, native_pagerank, native_sssp,
    native_tc)
