"""Plain references, one module per kernel, independent of the program."""
