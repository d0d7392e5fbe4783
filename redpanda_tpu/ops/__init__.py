"""Device kernels: batched consensus math and data-plane validation.

- quorum: the 50k-partition commit-index sweep (north star)
- crc32c: batched record-batch CRC validation
"""

from .quorum import (
    fold_replies,
    fold_replies_jit,
    follower_commit_step,
    follower_commit_step_jit,
    heartbeat_tick,
    heartbeat_tick_jit,
    local_append_update,
    local_append_update_jit,
    quorum_commit_step,
    quorum_commit_step_jit,
)
from .crc32c import crc32c_batch_device, crc32c_device

__all__ = [
    "fold_replies",
    "fold_replies_jit",
    "follower_commit_step",
    "follower_commit_step_jit",
    "heartbeat_tick",
    "heartbeat_tick_jit",
    "local_append_update",
    "local_append_update_jit",
    "quorum_commit_step",
    "quorum_commit_step_jit",
    "crc32c_batch_device",
    "crc32c_device",
]
