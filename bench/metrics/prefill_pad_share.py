"""Padded positions over positions dispatched to prefill: bucket padding
plus the empty rows of a packed prefill group, counted from the prompt
lengths and the gateway's buckets."""


def read(rec):
    pre = rec["work"].get("prefill")
    if not pre or not pre.get("positions"):
        return None
    return 100.0 * (1.0 - pre["rows"] / pre["positions"])
