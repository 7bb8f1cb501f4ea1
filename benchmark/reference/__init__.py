"""Plain references, one file per configuration (``<config>.py``), and
what they share. Nothing here imports the program."""
