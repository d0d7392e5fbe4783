"""Template makers for batches that compress (see templates/plain.py for
how a maker is named and called)."""

from __future__ import annotations

from functools import partial

from benchmark.reference import RewrittenTemplate, Stored, Template, make_templates

#: values of `compression.type` that leave a plain batch as it was sent
PASS_THROUGH = ("", "producer", "none", "uncompressed")


def topic_codec(config: dict) -> str | None:
    """The codec the configuration's topics set, or None where they
    pass batches through. The generator sends every template to every
    topic, so the topics have to agree."""
    wanted = {
        str((t.get("configs") or {}).get("compression.type", "")).lower()
        for t in config["topics"]
    }
    if len(wanted) != 1:
        raise ValueError(f"the topics set different codecs: {sorted(wanted)}")
    (codec,) = wanted
    return None if codec in PASS_THROUGH else codec


def random_share(seed: int, traffic: dict, config: dict) -> list[Template]:
    """`count` batches of the traffic's `batch_records` records of the
    configuration's `record_bytes` bytes whose values are random for
    the first `random_share` of their bytes and zero for the rest, as
    OpenMessaging Benchmark makes payloads that compress
    (`useRandomizedPayloads`, `randomBytesRatio`; `count` stands for its
    `randomizedPayloadPoolSize`). Where the topic sets a codec the
    templates say what a rewritten batch has to hold
    (`RewrittenTemplate`), else what a plain one does."""
    spec = traffic["templates"]
    share = float(spec["random_share"])
    if not 0.0 <= share <= 1.0:
        raise ValueError(f"random_share {share} is no share")
    codec = topic_codec(config)
    make = Template if codec is None else partial(RewrittenTemplate, stored=Stored(codec))
    return make_templates(
        seed, int(spec["count"]), int(traffic["batch_records"]),
        int(config["record_bytes"]), random_share=share, make=make,
    )
