"""The benchmark's yardstick: traffic, statistics, trace reduction,
peaks and the comparison that decides ``correct``. Nothing here imports
the program; the files under ``kinds/`` and the ``*.program.py``
recipes are the only ones that drive it."""
