"""Every backend compile after the window opened, however short
(`window_builds.compiles`, JAX's own event; what
`paging.jits_in_window` reads for the paged cell): a host loop that
slices a buffer to a level's size builds a program for every new size,
one a level past the warm-up's depth, with the dispatch window drained
and the chip idle.  The key is older than the metric."""


def read(obs, trace, cell):
    builds = obs.get("window_builds")
    return None if builds is None else builds["compiles"]
