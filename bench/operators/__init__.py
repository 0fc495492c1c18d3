"""Operator families, one module each, found by a configuration's
``family`` key: ``build(cfg, seed, sharding)`` returns ``(offsets,
bands)`` with the bands made on the device in one jitted call."""
