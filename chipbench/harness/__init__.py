"""The benchmark's harness: cells by name, the window, the trace, the line."""
