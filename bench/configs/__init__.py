"""Configurations: ``<name>.json`` holds the sizes as run, ``<module>.py``
builds the banks and inputs from the seed and hands them to the program,
``<module>_ref.py`` is the plain reference."""
