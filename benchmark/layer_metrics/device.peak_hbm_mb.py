"""Peak bytes in use on the fullest device after the window."""

import gate


def read(obs, trace, cell):
    peak = gate.peak_bytes(cell.devices)
    return peak / 1e6 if peak else None
