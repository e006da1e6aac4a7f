"""One reader per per-layer metric (``<name>.py``: ``read(obs)``), found by name; ``_serve`` and ``_flash`` hold what several share."""
