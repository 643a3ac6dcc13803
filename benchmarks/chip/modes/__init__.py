"""Cell drivers, one per ``mode`` named in a workload file."""
