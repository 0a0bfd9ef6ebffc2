"""The benchmark's own code: everything that decides a number lives here,
under ``paths``, and imports nothing of the program except in the drivers
and the ``program`` half of a model file."""
