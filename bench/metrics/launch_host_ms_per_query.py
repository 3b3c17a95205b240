"""Host self time of the kernel wrappers' "launch" spans (operand build
and upload, dispatch, a compile if one happens, output post-processing)
in the traced window, per window query."""
from spans import self_ms_per_query


def read(run):
    return self_ms_per_query(run, "dxpta.launch")
