from repro.kernels.segment_runs.ops import segment_runs, segment_runs_pallas
from repro.kernels.segment_runs.ref import segment_runs_ref
