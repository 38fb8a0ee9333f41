"""The chip benchmark's harness: ``catalog`` finds a cell's files by name,
``streams`` makes its traffic from the seed, ``program`` is every touch
point with the store under test, ``cell`` runs set-up, passes and the
window and checks them against ``reference``, ``trace`` reduces the
profiler's trace, and ``cli`` is the command."""
