"""FLOP and byte counts of each kernel or step, from its shapes."""
