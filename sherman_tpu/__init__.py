"""sherman_tpu — a TPU-native disaggregated-memory B+Tree framework.

A from-scratch reimplementation of the capabilities of Sherman (SIGMOD'22, a
write-optimized distributed B+Tree on disaggregated memory over one-sided
RDMA; reference at /root/reference) designed TPU-first:

- The "disaggregated memory pool" is HBM sharded across a ``jax.sharding.Mesh``
  of TPU chips; the one-sided RDMA verb layer (reference ``src/rdma/``,
  ``include/DSM.h``) becomes :class:`sherman_tpu.parallel.dsm.DSM`, a batched
  SPMD transport whose READ/WRITE/CAS/FAA requests ride XLA ``all_to_all``
  collectives over ICI.
- The NIC on-chip lock words (reference ``Common.h:86-93``,
  ``DirectoryConnection.cpp:24-30``) become a per-chip lock table shard with
  per-step linearized CAS semantics.
- ``Tree::search/insert`` (reference ``src/Tree.cpp``) become *batched* device
  kernels: a batch of keys walks the tree level-by-level under ``jit`` inside
  ``shard_map``; coroutine latency-hiding (reference ``Tree.cpp:1059-1122``)
  is subsumed by batching.

See SURVEY.md for the full reference analysis this build follows.
"""

from sherman_tpu.config import DSMConfig, TreeConfig

__version__ = "0.1.0"

__all__ = ["DSMConfig", "TreeConfig", "__version__"]
