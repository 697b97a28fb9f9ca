"""The paper's own workload as a production config: the distributed
mini-batch kernel k-means service (repro.core.distributed) at cluster scale
— e.g. clustering LM hidden states / embedding tables.

``chip_smoke.py`` and ``tests/test_tpu_compile.py`` drive the fit at
these widths."""
from repro.core.minibatch import MBConfig

# Production-scale clustering: 256 centers over d=1024 embeddings,
# batch 8192/iteration, window tau = b (the paper's practical regime:
# tau <= b works well, §6 "even tiny tau far below theory").
CONFIG = MBConfig(
    k=256,
    batch_size=8192,
    tau=8192,
    rate="beta",
    sqnorm_mode="recompute",    # paper-faithful baseline
    eval_mode="direct",
    epsilon=1e-4,
    max_iters=200,
)

EMBED_DIM = 1024
KAPPA = 2.0
