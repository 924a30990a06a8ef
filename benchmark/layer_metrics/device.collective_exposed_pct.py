"""Share of the traced window in which a collective operation ran on a
device while no other operation did, averaged over the devices.  Layer:
device (the in-graph gradient all-reduce of the data-parallel step)."""


def read(run):
    trace = run["trace"]
    if not trace or not trace["devices"] or not trace["window_s"]:
        return None
    if not any(d["collective_s"] for d in trace["devices"]):
        return None
    exposed = sum(d["collective_exposed_s"] for d in trace["devices"]) \
        / len(trace["devices"])
    return 100.0 * exposed / trace["window_s"]
