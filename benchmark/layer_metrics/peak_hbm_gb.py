"""Peak bytes on the fullest device after the window, in GB (1e9 bytes):
``peak_bytes_in_use + peak_bytes_reserved`` of ``memory_stats()``, the
result line's ``memory_peak_bytes`` (`run.memory_peak`: on the TPU a running
program's temporaries are reserved, not "in use")."""


def read(trace, facts):
    peak = facts["device"]["memory_peak_bytes"]
    return peak / 1e9 if peak else None
