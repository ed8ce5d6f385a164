"""Plain float32 references of the benchmark's configurations, computed in
blocks that fit on the chip beside the program's state."""
