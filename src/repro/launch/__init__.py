"""Launchers: production mesh, multi-pod dry-run, train/serve drivers."""
