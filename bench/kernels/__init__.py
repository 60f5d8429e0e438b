"""Bytes and operations each kernel of the program has to move or do,
computed from its shapes: the numerators of the kernel rooflines."""
