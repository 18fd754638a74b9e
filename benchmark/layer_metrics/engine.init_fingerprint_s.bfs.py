"""`engine.init_fingerprint_s` in a bfs-timed cell, where it moves
`distinct_per_s`: read from the window's run."""

import cells

read = cells.load_plugin("layer_metrics", "engine.init_fingerprint_s").read
