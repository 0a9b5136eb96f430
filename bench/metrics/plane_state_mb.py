"""Data plane state (``Overlord.memory_report()["total_ex_shadows"]``:
loaders, constructors, planner) at the window's end, in MB."""


def read(w):
    return w.plane_bytes / 1e6
