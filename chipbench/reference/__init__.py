"""Plain references: they import nothing of the program under test."""
