"""Plain float32 references the benchmark compares the program against.

Nothing here imports the program under test (``repro``)."""
