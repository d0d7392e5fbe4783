"""Template makers for uncompressed topics. A traffic file names its
maker (`"templates": {"maker": "plain.incompressible", ...}`); a maker
takes the seed, the traffic and the configuration and returns the
pre-encoded batches (benchmark/reference.py: `Template`) that the
generator sends and the comparison holds the answers to."""

from __future__ import annotations

from benchmark.reference import Template, make_templates


def incompressible(seed: int, traffic: dict, config: dict) -> list[Template]:
    """`count` batches of the traffic's `batch_records` records of the
    configuration's `record_bytes` bytes, random values: nothing
    compresses."""
    return make_templates(
        seed, int(traffic["templates"]["count"]), int(traffic["batch_records"]),
        int(config["record_bytes"]),
    )
