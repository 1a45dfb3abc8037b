"""Launcher: production meshes, multi-pod dry-run, train/serve CLIs."""
