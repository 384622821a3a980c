"""The share of the lane-steps the window's batches executed that served
no request: the idle lanes of a batch holding fewer requests than its
width, and the steps a lane stays frozen after its own nt while the
batch runs its longest lane's. 100 * (1 - lane_steps_useful /
lane_steps_executed), the service's counters over the window. %;
nothing to read from a service without them."""


def read(run):
    p = getattr(run.window, "pipeline", None) or {}
    executed, useful = p.get("lane_steps_executed"), p.get("lane_steps_useful")
    if not executed or useful is None:
        return None
    return 100.0 * (1.0 - useful / executed)
