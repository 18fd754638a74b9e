"""The exchange against the chip's interconnect: bytes of the traced
slice's all_to_all that left a chip, per chip, over the seconds the
chip spent in collectives, over the peak of `peaks.json`
(`ici_gbit_per_s` / 8).

Bytes: the program counts them (`exchange_offchip_bytes` gauge) as
`all_to_all_offchip_bytes` below reckons them; a program without the
gauge reports no roofline.  Seconds: `exchange.collective_share`'s, so
the votes' all-reduces are in the time and not in the bytes, and a tile
that was voted down and run again is in the time only: the share reads
low, never high."""

import cells


def all_to_all_offchip_bytes(tiles, chips, bucket_cap, row_bytes):
    """Bytes that leave a chip, summed over the chips, in `tiles`
    committed tiles of the sharded step: every tile, every chip sends
    one bucket of `bucket_cap` rows to each of the other `chips` - 1
    owners, padding included (the bucket for itself stays).  A row is
    the packed state (4 bytes a word) + 16 of fingerprint + 1 of mask
    + 12 of trace meta."""
    return tiles * chips * (chips - 1) * bucket_cap * row_bytes


def read(obs, trace, cell):
    doc = obs.get("metrics_doc")
    offchip = doc["gauges"].get("exchange_offchip_bytes") if doc else None
    secs = cells.load_plugin(
        "layer_metrics", "exchange.collective_share").collective_seconds(
            trace)
    if not offchip or not secs or not cell.peaks:
        return None
    peak = cell.peaks["ici_gbit_per_s"] / 8 * 1e9      # bytes/s a chip
    return 100.0 * offchip / len(cell.devices) / secs / peak
