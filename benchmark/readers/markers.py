"""Per-layer metrics of the transaction coordinator's marker fan-out,
read from the raw span records a traced run keeps (readers/hostspans.py
says where they come from). EndTxn delivers one control batch to each
data partition of the transaction under a `tx.marker` span, all of them
under one `tx.markers` span tagged with their number, `partitions`
(cluster/tx_coordinator.py `_complete`). A program without `tx.marker`
(the parent of the PR that added it), a run that kept no raw records or
dropped some: the reader then returns None."""

from __future__ import annotations

import statistics

from benchmark import log
from benchmark.readers.hostspans import DUR, ID, NAME, PARENT, TAGS, _raw


def overlap(ctx: dict, params: dict):
    """Σ durations of the `params["part"]` spans over Σ durations of
    the `params["whole"]` spans that hold them: about 1 (a little under,
    the group marker's share) while the parts go one after another,
    toward the fan-out while they go side by side. Each whole counts with
    all the parts under it (its `partitions` tag says how many), so that
    the window's edges cut none. Logs the mean `partitions` tag of the
    wholes and the mean `rows` of the window's `tick.upload` folds beside
    it."""
    raw = _raw(ctx)
    for span, tag in ((params["whole"], "partitions"), ("tick.upload", "rows")):
        values = [s[TAGS][tag] for s in raw
                  if s[NAME] == span and s[TAGS] and tag in s[TAGS]]
        if values:
            log(f"markers: mean {tag} on {span}: {statistics.fmean(values):.3f} "
                f"over {len(values)}")
    wholes = {s[ID]: s for s in raw if s[NAME] == params["whole"]}
    parts: dict = {}
    for s in raw:
        if s[NAME] == params["part"] and s[PARENT] in wholes:
            parts.setdefault(s[PARENT], []).append(s[DUR])
    # a whole whose first parts ended before the window opened has
    # fewer recorded than its `partitions` tag says: left out
    whole = [i for i, ds in parts.items()
             if len(ds) == (wholes[i][TAGS] or {}).get("partitions")]
    if not whole:
        return None
    return (float(sum(sum(parts[i]) for i in whole))
            / float(sum(wholes[i][DUR] for i in whole)))
