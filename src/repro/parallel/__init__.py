"""`repro.parallel` — sharding specs, parallel contexts, overlap schedules."""
from repro.parallel.context import (LOCAL, ParallelContext, activate,
                                    active_ctx, hint)
from repro.parallel.overlap import (overlapped_matmul_ag,
                                    overlapped_matmul_rs, software_pipeline)
from repro.parallel.pipeline import bubble_fraction, pipeline_apply
from repro.parallel.sharding import (batch_specs_sharding,
                                     cache_specs_sharding, make_context,
                                     param_specs)

__all__ = [
    "LOCAL", "ParallelContext", "activate", "active_ctx",
    "batch_specs_sharding", "bubble_fraction", "cache_specs_sharding",
    "hint", "make_context", "overlapped_matmul_ag", "overlapped_matmul_rs",
    "param_specs", "pipeline_apply", "software_pipeline",
]
