"""Plain references the timed paths are compared with. They import nothing of the program."""
