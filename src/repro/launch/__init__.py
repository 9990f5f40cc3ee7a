"""Launchers: mesh construction, train/serve drivers, the TCP fleet."""
