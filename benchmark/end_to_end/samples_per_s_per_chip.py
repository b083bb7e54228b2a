"""Training examples whose update was applied, per second, per chip: the
median of the window's readings — one reading per dispatch (resident cells)
or per completed task (job cells), each the examples of that unit over the
host-clock seconds from the end of the unit before it to its own end. The
median, not examples over the whole wall: on a host whose cores are shared
one stalled unit would otherwise move a 30 s run by 1% (PR 22). The rate
over the whole wall is printed on an earlier line."""


def read(run):
    w = run["window"]
    return w["samples_per_s"] / w["chips"]
