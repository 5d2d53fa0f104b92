"""HBM the timed train step holds on each device, from the compiler's
``memory_analysis()`` of the executable the window runs: arguments plus
outputs, less what the outputs alias, plus temporaries, in GiB."""


def read(run):
    m = run.compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             - m.alias_size_in_bytes + m.temp_size_in_bytes)
    return total / 2**30
