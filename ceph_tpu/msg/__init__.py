"""Host-level distributed communication — the ``src/msg`` analog.

The reference fans EC sub-ops to remote OSDs through AsyncMessenger's
ProtocolV2 framed wire protocol (msg/async/ProtocolV2.h: segmented
frames, per-segment crc32c). The TPU framework splits that role in two
(SURVEY.md section 5.8):

- intra-slice shard fan-out rides ICI as XLA collectives
  (``ceph_tpu.parallel``) — no host messaging at all;
- host-to-host (the data-center network) uses this package: the same framed,
  crc-protected wire protocol carrying typed, versioned sub-op
  messages between shard servers.

``NetShardBackend`` is a drop-in ``ShardBackend`` whose sub-ops travel
over sockets, so the whole RMW/read/recovery pipeline runs unchanged
against remote shard daemons — the standalone-cluster test tier
(qa/standalone/erasure-code) boots exactly that topology in-process.
"""

from .wire import BadFrame, decode_frame, encode_frame
from .messages import (
    ECSubRead,
    ECSubReadReply,
    ECSubWrite,
    ECSubWriteReply,
    decode_message,
)
from .messenger import Connection, Messenger
from .shard_server import NetShardBackend, ShardServer

__all__ = [
    "BadFrame",
    "decode_frame",
    "encode_frame",
    "ECSubRead",
    "ECSubReadReply",
    "ECSubWrite",
    "ECSubWriteReply",
    "decode_message",
    "Connection",
    "Messenger",
    "NetShardBackend",
    "ShardServer",
]
