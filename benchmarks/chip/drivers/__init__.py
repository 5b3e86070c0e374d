"""Drivers: one module per kind of traffic, each a ``Cell`` class."""
