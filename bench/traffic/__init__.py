"""Traffic mixes: ``<mix>.json`` holds a mix's parameters and names, under
``generator``, the module here that reads them.  Every generator builds
from ``(mix, seed, vocab)`` an object with the same small interface
(see ``sessions.py``), so the harness drives any mix alike."""
