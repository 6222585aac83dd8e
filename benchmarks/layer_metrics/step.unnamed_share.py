"""Device time of operations that carry no name of the program
(ray_tpu/util/tracing.py: BODY, MIXERS, SCOPES) over device busy time, device
0: the coverage of the scope tree, which a function added outside every
module and scope shows in. An event the compiled text gives no path is read
by its fusion's body, else by the instructions that take its result, else by
those it reads (lib/step_table.py); what none reaches counts here too, and an earlier
line says the two apart and prints the whole step by part, scope and pass.
A program from before PR 50 gave its loss no name: it reads the loss here."""
from benchmarks.lib import step_table


def read(run):
    found = step_table.note(run)
    if found is None:
        return None
    return 100.0 * sum(found["unnamed"].values()) / found["busy_s"]
