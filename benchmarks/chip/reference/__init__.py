"""The benchmark's plain reference: the faithful MAESTRO engine.

The modules beside this file are copies of ``src/repro/core``'s faithful
engine (``model.analyze`` and what it needs), so that the yardstick stays
fixed while the program changes. They work in exact Python arithmetic:
integer counts and float64 energies. Nothing here imports the program.
"""
