"""Plain references: one file an architecture, importing nothing of the
program."""
