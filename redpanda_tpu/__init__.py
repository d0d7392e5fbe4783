"""redpanda_tpu — a TPU-native streaming data platform.

A brand-new framework with the capabilities of the reference
(sarvex/redpanda, a Kafka-API-compatible, Raft-replicated streaming
broker): host data plane in Python-async + native C++ hot paths, with
all per-partition consensus state laid out as struct-of-arrays and
stepped by batched JAX/XLA/Pallas kernels — quorum/commit decisions for
tens of thousands of partitions in one device call.

Layer map (mirrors SURVEY.md §1):
  utils/        foundation: iobuf, crc32c, vint, named types
  compression/  codec registry (gzip/snappy/lz4/zstd + device backend slot)
  models/       record/record_batch data model + consensus state tensors
  ops/          device kernels: batched quorum, batched crc32c, codecs
  parallel/     device mesh, shardings, collective cluster step
  storage/      kvstore + segment log engine
  rpc/          framed async RPC with correlation multiplexing
  raft/         per-partition consensus; scalar + TPU batched backends
  cluster/      controller, topic table, partition/shard management
  kafka/        Kafka wire protocol, server handlers, internal client
"""

__version__ = "0.1.0"

# The one place that configures JAX for the whole package.
import os as _os

import jax as _jax

# Offsets/terms are int64 end-to-end across the device tensors; enable
# x64 at package init so no module depends on import order for it.
_jax.config.update("jax_enable_x64", True)

# Persistent compile cache. The tick and codec kernels are large XLA
# programs, and a cold compile runs synchronously on the event loop,
# so every process of a deployment (and every leg of chip_smoke.py)
# shares one cache. JAX_COMPILATION_CACHE_DIR, when set, is JAX's own
# setting and wins untouched; otherwise the cache sits at a fixed path
# inside the checkout — a cache that moves between runs never hits.
# A process pinned to the CPU (JAX_PLATFORMS=cpu: the tests, the
# tools/ smokes) gets none: XLA:CPU compiles these programs in
# seconds, and every load of a cached XLA:CPU executable prints a
# machine-feature warning from its AOT loader.
if (
    not _os.environ.get("JAX_COMPILATION_CACHE_DIR")
    and _jax.config.jax_platforms != "cpu"
):
    _jax.config.update(
        "jax_compilation_cache_dir",
        _os.path.join(
            _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
            ".jax_cache",
        ),
    )
