"""EPS (``core/eps.py``, ``engine/placement.py``): host memory the
compiled, donated train step holds by ``memory_analysis()``: host
arguments + host temp.  Moves ``peak_host_gb``."""


def read(ctx):
    m = ctx["step_memory"]
    return (m.host_argument_size_in_bytes + m.host_temp_size_in_bytes) / 1e9
