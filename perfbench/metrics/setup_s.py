"""Process start to the first timed admission: imports, training, the
dispatcher's AOT warm-up and the warm-up replay, compilation included."""


def read(w):
    return w.setup_s
