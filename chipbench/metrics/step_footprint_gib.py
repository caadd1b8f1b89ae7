"""Device memory the compiled train step needs per chip: arguments +
outputs - aliased + temporaries, from the executable's memory_analysis()."""


def read(f):
    m = f.memory
    return (m["argument"] + m["output"] - m["alias"] + m["temp"]) / 2 ** 30
