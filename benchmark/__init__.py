"""The benchmark: cells of one configuration under one traffic mix, measured
on the chip. Entry point: ``python3 -m benchmark.run`` (see BENCHMARK.json)."""
