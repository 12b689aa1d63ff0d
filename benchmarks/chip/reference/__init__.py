"""The plain reference of the chip benchmark: a copy of the scalar MIND
rack emulator that imports nothing of the program (see ``emulator.py``)."""
