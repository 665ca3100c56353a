"""Trace reducers, one module each, found by the name a per-layer metric
file gives under ``reader.reducer``.  A module exposes ``reduce(ctx) ->
dict`` (ctx: see ``device.py``); a later PR adds a file, never edits one."""
