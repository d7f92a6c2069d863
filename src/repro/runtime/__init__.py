"""Distributed runtime: health/failover, elastic scaling, stragglers,
gradient compression, compute/comm overlap."""
from repro.runtime.compression import (cross_pod_allreduce, compress_tree,  # noqa: F401
                                       decompress_tree, dequantize,
                                       init_errors, quantize)
from repro.runtime.elastic import (ElasticBudget, rebuild_overlay,  # noqa: F401
                                   remesh)
from repro.runtime.health import HealthMonitor  # noqa: F401
from repro.runtime.overlap import IngestStager, microbatched_grads  # noqa: F401
from repro.runtime.straggler import StragglerDetector  # noqa: F401
