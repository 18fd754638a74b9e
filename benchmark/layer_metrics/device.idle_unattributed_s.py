"""Seconds of the longest device-idle gaps that no host span covers
(`no host span` in the reducer's idle_gaps): time in which the device
waited and the program cannot say for what.  The reducer lists the ten
names with most idle seconds; where `no host span` is not among them
it is below the tenth and reads 0."""

NO_SPAN = "no host span"


def read(obs, trace, cell):
    if not trace or "idle_gaps" not in trace:
        return None
    return sum(secs for name, secs in trace["idle_gaps"]
               if name == NO_SPAN)
