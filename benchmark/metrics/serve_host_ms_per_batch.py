"""Host time of the service's pipeline a batch: its assemble, dispatch,
fetch and resolve stages less the fetch's wait on the device
(`fetch_wait_s`), over the batches resolved in the window, from the
service's pipeline_stats(). ms; nothing to read from a service that
does not split the wait out of its fetch."""


def read(run):
    p = getattr(run.window, "pipeline", None) or {}
    if "fetch_wait_s" not in p or not p.get("batches"):
        return None
    host_s = (p["assemble_s"] + p["dispatch_s"] + p["fetch_s"]
              - p["fetch_wait_s"] + p["resolve_s"])
    return 1e3 * host_s / p["batches"]
