"""The chip benchmark of the OTA-FL engine (see ``BENCHMARK.json`` at the
checkout's root and ``python3 -m bench.run --help``)."""
