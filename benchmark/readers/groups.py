"""Per-layer metrics read from the group coordinator's counters
(`devplane.status()["group_coordinator"]`, taken when the window closes;
`devplane.reset()` zeroed them when it opened: redpanda_tpu/observability/
devplane.py, GROUP_EVENTS). A program without them (the parent of the PR
that added them) has no such key: the reader then returns None."""

from __future__ import annotations


def event_count(ctx: dict, params: dict):
    """How many times the event `params["event"]` happened in the
    window: `rebalances` is the group's generation bumps."""
    events = (ctx.get("devplane") or {}).get("group_coordinator")
    if not events or params["event"] not in events:
        return None
    return float(events[params["event"]])
