"""Launchers: end-to-end train/serve drivers, step functions, input specs."""
