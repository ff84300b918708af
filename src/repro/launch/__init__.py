"""Launchers: production mesh, multi-pod dry-run, train/serve drivers.

Importing any of these modules sets no environment variable and touches no
device: ``dryrun.main`` sets ``XLA_FLAGS`` (512 host devices) itself, so
only a process that runs the dry-run gets the fake CPU devices.
"""
