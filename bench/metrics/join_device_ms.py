"""Device time of the join's programs per traced query (device trace);
the mix names them under `"programs": {"join": ...}`. Read it beside
`collect_device_ms`: a join program that is renamed or replaced leaves
this one and stays in that one."""

from lib import layers


def compute(run):
    if "join" not in run["traffic"].get("programs", {}):
        return None
    s = layers.device_seconds_per_query(run, "join")
    return None if s is None else 1e3 * s
