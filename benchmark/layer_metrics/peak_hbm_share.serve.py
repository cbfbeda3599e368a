"""Device: the fullest chip's memory peak over `bytes_limit`. The peak is
`peak_bytes_in_use + peak_bytes_reserved`, the result line's
`memory_peak_bytes`: the v5e's runtime counts a loaded program's
temporaries as reserved, not as in use (`tests/memory_probe.py`). The
line's `device` gives the two apart."""


def read(facts):
    if not facts["memory_limit_bytes"]:
        return None
    return 100.0 * facts["memory_peak_bytes"] / facts["memory_limit_bytes"]
