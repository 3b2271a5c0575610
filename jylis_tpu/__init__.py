"""jylis-tpu: a TPU-native distributed in-memory database for delta-state CRDTs.

Functional equivalent of the jylis reference (a masterless CRDT database
speaking the Redis RESP protocol; see /root/reference/README.md:3-6), built
TPU-first: every CRDT keyspace is a struct-of-arrays tensor resident on the
accelerator, and the anti-entropy merge hot path (reference:
jylis/cluster.pony:250-252 -> repo_manager.pony:92-93) is a batched XLA
lattice-join kernel instead of a sequential per-key loop.

Layering (mirrors SURVEY.md section 1, re-designed for JAX/XLA):

  utils/     config, logging, name generation          (reference L0)
  ops/       CRDT lattice kernels, jit/vmap-able       (reference L2, pony-crdt)
  models/    per-type repos + database router          (reference L3/L4)
  cluster/   gossip membership + anti-entropy          (reference L5)
  server/    RESP protocol server                      (reference L6)
  parallel/  mesh sharding of the keyspace (pjit)      (no reference analog;
             scale-out of the merge path across chips)

64-bit integers are required by the data-type semantics (u64 timestamps and
counters, docs/_docs/types/*.md), so x64 mode is enabled at import.
"""

import os

import jax

jax.config.update("jax_enable_x64", True)

# Persistent compile cache, set here because every entry point (the node,
# the smokes, the tests) imports this package first.
# JAX_COMPILATION_CACHE_DIR, when set, places the cache and jax reads it
# itself; otherwise a FIXED directory beside the package (gitignored),
# never a temp/pid/time-derived one — a cache that moves never hits.
# Threshold 0: most serving kernels compile in under jax's default 1 s
# floor, and a boot pays for all of them (database.warmup).
COMPILE_CACHE_DIR = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

__version__ = "0.5.0"
