"""Launchers: train (data-parallel training) and serve (decode)."""
