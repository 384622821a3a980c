"""Device time a step of the compute ops that are no kernel and carry
no `layer` tag (benchmark/layers.py): in a one-chip program, the work
the compiler inserted, such as the copy of a loop carry. ms, from the
device trace.

Nothing to read from a program that tags no op, nor from a trace of
several chips: in a program partitioned over chips XLA drops the tags
of the exchange's and the splice's data movement (layers.py says which
ops), so there this class would charge the schedule driver with their
time."""

from benchmark import layers


def read(run):
    tr = run.trace
    if tr is None or len(tr.chips()) > 1 or not layers.is_tagged(tr):
        return None
    return layers.ms_per_step(
        run, lambda o: layers.layer_of(o) == "untagged")
