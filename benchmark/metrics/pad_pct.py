"""Padded set rows over launched set rows, in percent, from the launch
ledger's `dispatch` records (the backend's bucketed shapes)."""


def read(ctx):
    recs = [r for r in ctx.launches if r["kind"] == "dispatch"]
    launched = sum(r["padded_sets"] for r in recs)
    if not launched:
        return None
    return 100.0 * sum(r["padded_sets"] - r["real_sets"] for r in recs) / launched
