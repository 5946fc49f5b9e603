"""How the engine's kernels are named in a device trace.

An op matches when its own name (``tracing.op_name``: ``schwarz_fwd.9``,
``gram.3``; the Pallas ``name`` of the kernel with XLA's suffix) or its
naming stats match.  The modules tell the timed solve and factor build
from the autotune sweeps, which launch the same kernels from modules of
their own (jit_schwarz_fwd, jit_gram, ...).  Names read off a recorded
v5e trace (bench/tests/data/v5e_trace_slice.json).
"""

SCHWARZ_OPS = r"^schwarz_(fwd|bwd)\b"
GRAM_OPS = r"^gram\b"
SOLVE_MODULES = r"^jit_solve_vmapped\b|shard_map|shmap"
FACTOR_MODULES = r"^jit__factor_batched\b"
