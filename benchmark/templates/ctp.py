"""Templates for a consume-transform-produce pipeline (see
templates/plain.py for how a maker is named and called): one pre-encoded
plain batch that an idempotent source producer stamps with its producer
id, epoch and base sequence, and that the pipeline's member copies, as
`TransactionalMessageCopier` copies a record, into a transactional batch
of its own stamp. What has to come back of each is the template's to say.

The stamps are templates/transactional.py's (a CRC mended by CRC-32C's
linearity); only which attribute bits a stamp sets differs. Nothing of
the program is imported.
"""

from __future__ import annotations

import struct

from benchmark.reference import (
    AFTER_ATTRIBUTES, ATTRIBUTES_AT, BODY_AT, CRC_AT, make_templates,
)
from benchmark.templates.transactional import (
    _PRODUCER, AFTER_PRODUCER, PRODUCER_AT, TransactionalTemplate,
)
from benchmark.txreplay import TRANSACTIONAL_BIT


class CopiedTemplate(TransactionalTemplate):
    """A source batch and its copy. `stamp` is the source producer's: an
    idempotent producer's id, epoch and base sequence and the attributes
    as sent (Kafka's default since 3.0, no transaction). `stamp_copy` is
    the member's: the same records under its transactional producer's
    id, epoch and sequence, with the transactional bit. `came_back`
    holds a stored source batch and `copy_came_back` a stored copy to
    what TransactionalTemplate.came_back holds a batch to, each with its
    own attributes; the tolerance is none. The records section is the
    key of both, so a copy is known by the template of its source."""

    def _stamped(self, attributes: int, producer_id: int, epoch: int,
                 base_sequence: int) -> bytes:
        out = bytearray(self.wire)
        struct.pack_into(">h", out, ATTRIBUTES_AT, attributes)
        _PRODUCER.pack_into(out, PRODUCER_AT, producer_id, epoch, base_sequence)
        struct.pack_into(">I", out, CRC_AT, self._crc_of(out[BODY_AT:AFTER_PRODUCER]))
        return bytes(out)

    def stamp(self, producer_id: int, epoch: int, base_sequence: int) -> bytes:
        return self._stamped(self.attributes, producer_id, epoch, base_sequence)

    def stamp_copy(self, producer_id: int, epoch: int, base_sequence: int) -> bytes:
        return self._stamped(
            self.attributes | TRANSACTIONAL_BIT, producer_id, epoch, base_sequence)

    def _holds(self, batch: bytes, attributes: int) -> bool:
        if len(batch) != len(self.wire):
            return False
        length, _epoch, magic = struct.unpack_from(">iib", batch, 8)
        if magic != 2 or length != len(batch) - 12:
            return False
        (got,) = struct.unpack_from(">h", batch, ATTRIBUTES_AT)
        producer_id, epoch, base_sequence = _PRODUCER.unpack_from(batch, PRODUCER_AT)
        return (
            got == attributes
            and producer_id >= 0 and epoch >= 0 and base_sequence >= 0
            and batch[AFTER_ATTRIBUTES:PRODUCER_AT] == self.wire[AFTER_ATTRIBUTES:PRODUCER_AT]
            and batch[AFTER_PRODUCER:] == self.wire[AFTER_PRODUCER:]
            and struct.unpack_from(">I", batch, CRC_AT)[0]
            == self._crc_of(batch[BODY_AT:AFTER_PRODUCER])
        )

    def came_back(self, batch: bytes) -> bool:
        return self._holds(batch, self.attributes)

    def copy_came_back(self, batch: bytes) -> bool:
        return self._holds(batch, self.attributes | TRANSACTIONAL_BIT)


def incompressible(seed: int, traffic: dict, config: dict) -> list[CopiedTemplate]:
    """`count` batches of the traffic's `batch_records` records of the
    configuration's `record_bytes` bytes, random values."""
    return make_templates(
        seed, int(traffic["templates"]["count"]), int(traffic["batch_records"]),
        int(config["record_bytes"]), make=CopiedTemplate,
    )
