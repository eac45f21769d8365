import sys
from pathlib import Path

_ROOT = Path(__file__).resolve().parents[2]
for p in (str(_ROOT / "src"), str(_ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402

jax.config.update("jax_platform_name", "cpu")

#: a tiny deployment of the sift1m configuration, for CPU runs
TINY = {"data": {"n": 4096, "d": 32, "n_query": 64, "clusters": 4,
                 "intrinsic_dim": 4},
        "index": {"nlist": 16, "nprobe": 4, "kmeans_iters": 4},
        "serve": {"max_batch": 8}, "check": {"sample": 32}}
#: TINY made angular: unit vectors ranked by -q.x, ``n`` not a multiple of
#: ``clusters``
ANGULAR = {"data": {"metric": "angular", "n": 4099}}
