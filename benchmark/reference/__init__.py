"""Plain references: copies of the scalar simulators, independent of the program."""
