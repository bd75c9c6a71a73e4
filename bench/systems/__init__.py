"""Adapters that drive the program under test, one module per
configuration ``system``, found by that name.  Each module has
``make(config, devices)``."""
