"""KV blocks in use over blocks reserved, summed over the window's ticks
that stepped: distinct blocks in live requests' tables over those plus the
reservation debt (``kv_blocks_used``, ``kv_blocks_reserved`` on each
``serve.tick`` span in the traced run's trace)."""
from perfbench import progspans


def read(run, name):
    a = (progspans.window(run) or {}).get("attrs", {})
    res = a.get("serve.tick:kv_blocks_reserved")
    return (100.0 * a["serve.tick:kv_blocks_used"] / res) if res else None
