"""The yardstick: traffic, clocks, the recording sink, stats deltas, the
trace reduction, peaks and byte counts, and the comparison that decides
``correct``. Only ``runner.py`` (and a configuration's ``build_graph``)
touches the program; everything else here is plain numpy."""
