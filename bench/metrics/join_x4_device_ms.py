"""Device time of the SPMD join's programs per traced query, mean over
the chips' planes (device trace). The mix names the programs under
`"programs": {"join": ...}`; `layers.device_seconds_per_query` sums
them over every plane (chip-seconds), so the mean over the chips that
ran anything is that sum by their number."""

from lib import layers


def compute(run):
    if "join" not in run["traffic"].get("programs", {}):
        return None
    chip_seconds = layers.device_seconds_per_query(run, "join")
    if chip_seconds is None:
        return None
    return 1e3 * chip_seconds / run["trace"]["chips"]
