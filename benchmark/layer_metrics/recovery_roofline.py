"""Recovery ladder, on the device: the least time the chip could take for
the rows the ladder's rule has to verify, whatever implements it (work.py:
the rows its combined checks took, `rows_checked`, as one flush, and the
leaf's rows, `leaf_rows`, as another), over the device time the ladder cost
(`recovery.device_ms`). A check spends a whole chunk on as few as 512 rows,
so the share reads far under `msm_roofline`'s. None where either part is
missing."""

import recovery_spans


def read(ctx):
    device_ms = recovery_spans.forged_device_ms(ctx)
    checked = recovery_spans.median_ladder(ctx, "rows_checked")
    leaf = recovery_spans.median_ladder(ctx, "leaf_rows")
    if device_ms is None or device_ms <= 0 or checked is None or leaf is None or ctx.peaks is None:
        return None
    least = sum(ctx.work.least_seconds(int(round(rows)), ctx.peaks)[0] for rows in (checked, leaf))
    return 100.0 * least * 1e3 / device_ms
