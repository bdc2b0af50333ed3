"""BandPilotDispatcher.aot_warm_seconds: the fused descent's AOT compile
(or cache read) at construction."""


def read(w):
    return w.aot_warm_s
