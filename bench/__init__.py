"""The chip benchmark of MuxFlow's co-location path (see BENCHMARK.json)."""
