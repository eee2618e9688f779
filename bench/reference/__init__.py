"""Plain float32 references of the served models, one module per family.
They import nothing of the program and take no array it made."""
