"""Device memory the compiled training step needs per chip, by the
compiler's own analysis: arguments, temporaries and the outputs that do
not alias an argument (GB, 1e9 bytes).  Not a measured peak: the
runtime's peak counter leaves the step's temporaries out."""


def read(ctx):
    m = ctx.report.memory
    if m is None:
        return None
    return (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes) / 1e9
