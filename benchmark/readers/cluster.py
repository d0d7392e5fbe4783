"""Per-layer metrics read from the brokers the harness holds."""

from __future__ import annotations


def elections_in_window(ctx: dict, params: dict):
    """Leadership terms won across the brokers between the window's
    opening and its close."""
    return float(ctx["elections_in_window"])
