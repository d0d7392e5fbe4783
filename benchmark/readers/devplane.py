"""Per-layer metrics read from the program's devplane counters
(`devplane.status()` taken when the window closes; `devplane.reset()`
was called when it opened)."""

from __future__ import annotations


def kernel_p50_ms(ctx: dict, params: dict):
    k = ctx["devplane"].get("kernels", {}).get(params["kernel"])
    if not k or not k.get("count"):
        return None
    return float(k["p50_ms"])


def h2d_bytes_per_acked_byte(ctx: dict, params: dict):
    up = ctx["devplane"].get("transfer_bytes", {}).get("h2d")
    acked = ctx["acked_payload_bytes"]
    if not up or not acked:
        return None
    return up / acked
