"""The chip benchmark of the sLDA system: `python3 bench/run.py --help`."""
