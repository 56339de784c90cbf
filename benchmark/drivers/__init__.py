"""One driver per kind of system under test; a configuration names its
driver (``"driver"``) and the harness imports it by that name."""
