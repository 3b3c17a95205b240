"""Host time blocked on launches ("launch.wait": device execution plus
readback) in the traced window, per window query."""
from spans import self_ms_per_query


def read(run):
    return self_ms_per_query(run, "dxpta.launch.wait")
