"""The benchmark's own code: everything a later PR may not move lives here."""
