"""Model step: HBM the compiled, donated train step needs by XLA's own
``memory_analysis()``: device arguments + outputs + temp - aliased
bytes.  Moves ``peak_hbm_gb``."""


def read(ctx):
    m = ctx["step_memory"]
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes) / 1e9
